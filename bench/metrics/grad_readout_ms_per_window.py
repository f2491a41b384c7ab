"""Device time per window of the update chunk's `grad_readout` stage:
readout, loss, cbar and the gradient contraction (`compact_grads`,
`learner.grads`)."""
from bench import stages


def read(ctx):
    return stages.device_ms(ctx, "grad_readout")
