"""The whole step's share of the chip's peak: the operations one stream
step requires (masked forward and readout, the ragged influence update
2 sum_b K_b K'_b Pc, the gradient readout 2 sum_b K_b Pc, at the measured
backward sparsity) times the stream steps of the traced windows, over their
host-clock span times the chip's published peak (bf16), in percent."""
from bench import costs
from bench.peaks import peak


def read(ctx):
    w = ctx["window"]
    beta = w["telemetry"].get("bwd_sparsity")
    if beta is None or ctx["trace"]["device"] is None:
        return None
    n = w["n"]
    per_step = costs.step_flops(w["batch"], n, w["n_out"], w["nnz_weights"],
                                w["Pc"], (1.0 - beta) * n)
    rate = per_step * w["steps"] / w["span_s"]
    return 100.0 * rate / peak(ctx["device_kind"])["flops_per_s"]
