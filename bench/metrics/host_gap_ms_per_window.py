"""Device-idle time between one update chunk's last op and the next chunk's
first op, per window: what the runtime loop (input assembly, dispatch,
readback, admission) leaves the device waiting for."""


def read(ctx):
    chunk = ctx["trace"]["chunk"]
    if chunk is None or chunk["runs"] < 2:
        return None
    return 1e3 * chunk["idle_between_s"] / (chunk["runs"] - 1)
