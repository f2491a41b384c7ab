"""Device time of the jitted update chunk (`online_update_chunk`, vmapped in
a fleet: forward, influence update, gradients, optimizer) per window: the
union of its ops' intervals over its executions in the traced span."""


def read(ctx):
    t = ctx["trace"]
    chunk = t["chunk"]
    if chunk is None:
        return None
    return 1e3 * chunk["busy_s"] / chunk["runs"]
