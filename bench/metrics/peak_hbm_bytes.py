"""The device's peak bytes in use (`memory_stats()["peak_bytes_in_use"]`),
read after the measured span and before the reference runs."""


def read(ctx):
    return ctx["peak_bytes"]
