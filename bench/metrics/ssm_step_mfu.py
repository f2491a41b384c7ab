"""The granite period's whole step as a share of the chip's peak: the
operations one stream step requires (`bench/mamba2_costs.py` `step_ops`:
the frozen forward, the top mixer, reverse mode through the tail, the
trace update and contraction) times the traced windows' stream steps, over
their host-clock span times the chip's published peak (bf16), in
percent."""
from bench import mamba2_costs
from bench.peaks import peak


def read(ctx):
    w = ctx["window"]
    if "model" not in w or ctx["trace"]["device"] is None:
        return None
    rate = mamba2_costs.step_ops(w) * w["steps"] / w["span_s"]
    return 100.0 * rate / peak(ctx["device_kind"])["flops_per_s"]
