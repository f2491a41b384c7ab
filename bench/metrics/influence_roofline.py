"""Least time of one window's exact influence updates over the update
chunk's device time per window, in percent.

Least time is the larger of the updates' operations over the chip's peak
and their bytes over its peak bandwidth (`bench/costs.py`, `bench/peaks.json`),
at the configuration's live column count Pc and the live rows
K = (1 - beta) n that the measured backward sparsity beta gives, for every
stream of the cell; so the count is the same whatever implements it."""
from bench import costs
from bench.peaks import peak


def read(ctx):
    t, w = ctx["trace"], ctx["window"]
    beta = w["telemetry"].get("bwd_sparsity")
    if t["chunk"] is None or beta is None:
        return None
    pk = peak(ctx["device_kind"])
    B, n, Pc = w["batch"], w["n"], w["Pc"]
    K = (1.0 - beta) * n
    flops = costs.ragged_influence_update_flops([K] * B, [K] * B, Pc)
    nbytes = costs.influence_update_bytes(B, K, K, Pc, n)
    per_step, bound = costs.least_time_s(flops, nbytes, pk["flops_per_s"],
                                         pk["hbm_bytes_per_s"])
    per_window = per_step * w["update_every"] * w["streams"]
    chunk_s = t["chunk"]["busy_s"] / t["chunk"]["runs"]
    ctx["log"].append(
        f"influence_roofline: bound by {bound}; per step {flops!r} flops, "
        f"{nbytes!r} bytes at K={K!r}, Pc={Pc}, B={B}; least "
        f"{per_window!r} s per window against {chunk_s!r} s of chunk")
    return 100.0 * per_window / chunk_s
