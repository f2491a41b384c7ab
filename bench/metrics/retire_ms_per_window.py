"""Host time per interval between windows spent retiring sessions
(`fleet.retire` spans: the slot freed and marked for the next batched
reset, on the host only)."""
from bench import stages


def read(ctx):
    return stages.host_ms(ctx, "fleet.retire")
