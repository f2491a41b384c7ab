"""Host time per interval between windows spent admitting sessions
(`fleet.admit` spans: slot claim, template copy, slot write)."""
from bench import stages


def read(ctx):
    return stages.host_ms(ctx, "fleet.admit")
