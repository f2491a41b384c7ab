"""Host time per interval between windows spent on the per-session loop
after the readback (`fleet.bookkeep` spans: positions, losses, telemetry,
events)."""
from bench import stages


def read(ctx):
    return stages.host_ms(ctx, "fleet.bookkeep")
