"""Device time per window of the update chunk's `ssm_trace_update` stage
(`repro.cells.mamba2`, `core/learner.py`): the self time of the ops under
that named scope, per execution of the chunk (`bench/stages.py`)."""
from bench import stages


def read(ctx):
    return stages.device_ms(ctx, "ssm_trace_update")
