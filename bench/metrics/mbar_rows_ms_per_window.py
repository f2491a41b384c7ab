"""Device time per window of the update chunk's `mbar_rows` stage: the
immediate influence M-bar at the active rows, built at compact width
(`flat_mbar_rows_cols`)."""
from bench import stages


def read(ctx):
    return stages.device_ms(ctx, "mbar_rows")
