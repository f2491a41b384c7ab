"""Device time per window of the update chunk's `j_tile_gather` stage: the
active rows (`compact_rows`), the J-hat tiles they need
(`gather_j_tiles`) and the active rows of hp."""
from bench import stages


def read(ctx):
    return stages.device_ms(ctx, "j_tile_gather")
