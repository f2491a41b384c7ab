"""Device time per window of the update chunk's `optimizer` stage: the
AdamW update and the reset of the gradient accumulators."""
from bench import stages


def read(ctx):
    return stages.device_ms(ctx, "optimizer")
