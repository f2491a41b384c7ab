"""Device time per window of the update chunk's `partials` stage: the
cell's forward step, J-hat and the M-bar partials (`cell_partials`)."""
from bench import stages


def read(ctx):
    return stages.device_ms(ctx, "partials")
