"""Host time per interval between windows spent assembling the window's
inputs from every live session's stream (`fleet.gather` spans)."""
from bench import stages


def read(ctx):
    return stages.host_ms(ctx, "fleet.gather")
