"""Device-idle time per interval between update chunks that no host span
explains: idle stretches under no span of the thread that runs the
windows (`bench/trace_reduce.py` gaps)."""
from bench import stages


def read(ctx):
    return stages.untraced_idle_ms(ctx)
