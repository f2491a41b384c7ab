"""Device time per window of the update chunk's `influence_update` stage:
the compact influence update J M + M-bar (`compact_update`, or the fused
kernel)."""
from bench import stages


def read(ctx):
    return stages.device_ms(ctx, "influence_update")
