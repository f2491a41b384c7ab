"""Device time per window of the update chunk's ops in none of its six
stages: telemetry, loop control, copies."""
from bench import stages


def read(ctx):
    return stages.device_ms(ctx, None)
