"""Host time per interval between windows spent admitting sessions
(`fleet.admit` spans: a template join claims a free slot and marks it for
the next batched reset; an explicit or resumed state is written at once)."""
from bench import stages


def read(ctx):
    return stages.host_ms(ctx, "fleet.admit")
