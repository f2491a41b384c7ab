"""Least time of one window's exact influence updates over the device time
of the update chunk's `influence_update` stage per window, in percent.

Least time is the larger of the updates' operations over the chip's peak
and their bytes over its peak bandwidth (`bench/costs.py`, `bench/peaks.json`),
at the configuration's live column count Pc and the live rows
K = (1 - beta) n that the measured backward sparsity beta gives, for every
stream of the cell; so the count is the same whatever implements it.  The
stage's time is the self time of the ops under its scope (`bench/stages.py`:
`compact_update`, or the fused Pallas kernel)."""
from bench import costs, stages
from bench.peaks import peak


def read(ctx):
    w = ctx["window"]
    beta = w["telemetry"].get("bwd_sparsity")
    stage_ms = stages.device_ms(ctx, "influence_update")
    if beta is None or not stage_ms:
        return None
    pk = peak(ctx["device_kind"])
    B, n, Pc = w["batch"], w["n"], w["Pc"]
    K = (1.0 - beta) * n
    flops = costs.ragged_influence_update_flops([K] * B, [K] * B, Pc)
    nbytes = costs.influence_update_bytes(B, K, K, Pc, n)
    per_step, bound = costs.least_time_s(flops, nbytes, pk["flops_per_s"],
                                         pk["hbm_bytes_per_s"])
    per_window = per_step * w["update_every"] * w["streams"]
    ctx["log"].append(
        f"influence_roofline: bound by {bound}; per step {flops!r} flops, "
        f"{nbytes!r} bytes at K={K!r}, Pc={Pc}, B={B}; least "
        f"{per_window!r} s per window against {stage_ms!r} ms of "
        f"influence_update")
    return 100.0 * per_window / (1e-3 * stage_ms)
