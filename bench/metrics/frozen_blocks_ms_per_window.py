"""Device time per window of the update chunk's `frozen_blocks` stage
(`repro.cells.mamba2`, `core/learner.py`): the self time of the ops under
that named scope, per execution of the chunk (`bench/stages.py`)."""
from bench import stages


def read(ctx):
    return stages.device_ms(ctx, "frozen_blocks")
