"""What one step of the granite period requires, from its shapes alone
(the counts the ssm metrics read; `bench/references/mamba2.py` says what
the step computes).  The same whatever implements it.

A window record carries the sizes (`bench/entries/online_ssm_stream.py`
`trace`): batch B, the published widths, the local heads [h0, h1), the
period's layer types.
"""
from __future__ import annotations

F32 = 4


def _dims(w: dict) -> dict:
    m = w["model"]
    h0, h1 = m["local_heads"]
    return {"B": w["batch"], "D": m["hidden_size"],
            "F": m["intermediate_size"], "V": m["vocab_size"],
            "H": m["mamba_n_heads"], "P": m["mamba_d_head"],
            "N": m["mamba_d_state"], "K": m["mamba_d_conv"],
            "Hq": m["num_attention_heads"],
            "Hk": m["num_key_value_heads"], "S": m["kv_slots"],
            "Hl": h1 - h0}


def trace_update(w: dict) -> tuple[float, float]:
    """(operations, bytes) of one step's trace update and contraction.

    Big traces (the x, B, dt rows: 3 of [B, Hl, P, N, D]): per element
    d*T + f*u and lam*T' summed, 5 operations, one read and one write.
    Small traces (conv taps and bias of x and B, A_log, dt_bias: [B, Hl, P,
    N] times 2K + 4): 4 operations, a read, a write, and their increment
    read.  The increments' factors and lam read once, the step's gradient
    written once."""
    d = _dims(w)
    B, Hl, P, N, D, K = (d[k] for k in ("B", "Hl", "P", "N", "D", "K"))
    state = B * Hl * P * N
    big = 3 * state * D
    small = state * (2 * K + 4)
    factors = (4 * state + B * Hl * P * D + B * Hl * P + B * N * D
               + B * D + B * Hl)
    grads = Hl * P * D + N * D + Hl * D + (Hl * P + N) * (K + 1) + 2 * Hl
    ops = 5.0 * big + 4.0 * small
    nbytes = F32 * (2.0 * big + 3.0 * small + factors + grads)
    return ops, nbytes


def step_ops(w: dict) -> float:
    """Operations one stream step requires: the frozen period's forward
    (every matmul at 2 per multiply-add, attention over the KV cache's
    slots), the top mixer's forward, reverse mode through the tail (the top
    layer's MLP and the tied head, input gradients, once more their
    forward), the readout side's weight gradients, the trace update and
    contraction."""
    d = _dims(w)
    B, D, F, V = d["B"], d["D"], d["F"], d["V"]
    H, P, N = d["H"], d["P"], d["N"]
    di = H * P
    mixer = D * (2 * di + 2 * N + H) + D * di
    attn = D * D + 2 * D * (d["Hk"] * D // d["Hq"]) + D * D
    mlp = 3 * D * F
    types = w["layer_types"]
    n_m = sum(t == "mamba" for t in types)
    n_a = len(types) - n_m
    weights = n_m * mixer + n_a * attn + len(types) * mlp + V * D
    forward = 2.0 * B * weights + n_a * 4.0 * B * d["Hq"] * d["S"] * (D // d["Hq"])
    tail = 2.0 * B * (mlp + V * D)
    readout = 2.0 * B * (D * di + D * di + D * N)
    return forward + tail + readout + trace_update(w)[0]
