"""Host time per interval between windows spent retiring sessions
(`fleet.retire` spans: slot reset to the template)."""
from bench import stages


def read(ctx):
    return stages.host_ms(ctx, "fleet.retire")
