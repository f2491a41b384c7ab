"""Least time of one window's trace updates over the device time of the
chunk's `ssm_trace_update` stage per window, in percent.

Least time is the larger of the updates' operations over the chip's peak
and their bytes over its peak bandwidth (`bench/mamba2_costs.py`: a read
and a write of every trace, the increments' factors, the gradient;
`bench/peaks.json`), k steps a window; the stage's time is the self time
of the ops under its scope (`bench/stages.py`)."""
from bench import mamba2_costs, stages
from bench.peaks import peak


def read(ctx):
    w = ctx["window"]
    stage_ms = stages.device_ms(ctx, "ssm_trace_update")
    if not stage_ms or "model" not in w:
        return None
    pk = peak(ctx["device_kind"])
    ops, nbytes = mamba2_costs.trace_update(w)
    t_ops, t_bytes = ops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"]
    per_window = max(t_ops, t_bytes) * w["update_every"] * w["streams"]
    ctx["log"].append(
        f"ssm_trace_roofline: bound by "
        f"{'bytes' if t_bytes >= t_ops else 'flops'}; per step {ops!r} ops, "
        f"{nbytes!r} bytes; least {per_window!r} s per window against "
        f"{stage_ms!r} ms of ssm_trace_update")
    return 100.0 * per_window / (1e-3 * stage_ms)
