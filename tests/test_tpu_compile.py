"""Compile the online RTRL path's kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot check what Mosaic enforces:
the (8, 128) block rule, which primitives lower inside a kernel, VMEM use.
Here the chip's own compiler, which is installed with JAX, compiles for a
v5e that is described and not attached:

  * `compact_fused.fused_update_pallas` at the shapes of chip_smoke.py's
    phase A (the paper's EGRU: n=16, batch 32, omega=0.9) and phase B
    (n=256, n_in=8, batch 4, omega=0.9), with an f32 and a bf16 carry;
  * `influence.influence_update_pallas` (backend="pallas") at phase B;
  * the jitted `online_update_chunk` of phases A and B, which must hold the
    fused kernel (`tpu_custom_call`);
  * the head-diagonal trace update of granite-4.0-h-micro's top Mamba-2
    mixer (`kernels/ssm_trace.py`) at the benchmark cell's shapes (6 rows,
    8 heads of 64, d_state 128, d_model 2048), and the whole online chunk
    of that period with its traces donated: one fusion reads and writes
    the three big traces, in place, and the chunk fits one v5e.

Each compile's `memory_analysis()` is recorded as a test property.  The
topology is described inside a module fixture, never at import, and the
tests skip where it cannot be described.  Nothing here runs: a compile that
passes is not a chip run.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import egru_spiral
from repro.core import cells, stacked_rtrl as ST
from repro.core.cells import stacked_config
from repro.core.learner import LearnerSpec, make_learner
from repro.kernels import compact
from repro.kernels import compact_fused as CF
from repro.kernels import ops as kops
from repro.kernels.ssm_trace import ssm_trace_update
from repro.optim import make_optimizer
from repro.optim.optimizers import masked
from repro.runtime.online import online_update_chunk

K_UPDATE = 8
# (n, n_in, batch) of chip_smoke.py's phases A and B, both at omega = 0.9
PHASES = {"A": (16, 2, 32), "B": (256, 8, 4)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back from the persistent
    # cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def kernel_path(monkeypatch):
    """The described chip still reports jax.default_backend() == 'cpu', so
    the engine would pick its XLA lowering: steer it onto the TPU kernel."""
    monkeypatch.setattr(CF, "_on_tpu", lambda: True)


def _phase(name):
    """Learner, optimizer and the (params, carry) shapes of one phase, set
    up as chip_smoke.py sets it up (the carry is built on the host: the
    column layout it derives from the masks is host-side work)."""
    n, n_in, batch = PHASES[name]
    layer = dataclasses.replace(egru_spiral.CONFIG, n_hidden=n, n_in=n_in,
                                batch_size=batch)
    cfg = stacked_config(layer, 1)
    key = jax.random.key(0)
    masks = ST.make_stacked_masks(cfg, jax.random.fold_in(key, 1), 0.9)
    params = ST.apply_stacked_masks(cells.init_stacked_params(cfg, key),
                                    masks)
    learner = make_learner(LearnerSpec(engine="stacked", cfg=cfg,
                                       backend="compact_fused",
                                       col_compact=True))
    carry = learner.init(params, masks, (jnp.zeros((batch, n_in)),
                                         jnp.zeros((batch,), jnp.int32)),
                         t_total=K_UPDATE)
    opt = masked(make_optimizer("adamw", lr=cfg.lr),
                 {"layers": masks, "out": None})
    shapes = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    return learner, opt, shapes(params), shapes(carry)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _record(record_property, compiled):
    ma = compiled.memory_analysis()
    record_property("memory_analysis", {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes})
    return ma


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("phase", ["A", "B"])
def test_fused_kernel_compiles_for_v5e(one_chip, kernel_path,
                                       record_property, phase, dtype):
    _, _, _, carry = _phase(phase)
    B, K, Pc_pad = carry["vals"].shape
    f32, i32 = jnp.float32, jnp.int32
    sd = lambda shape, dt=f32: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)
    compiled = CF.fused_update_pallas.lower(
        sd((B, K, K)), sd((B, K, Pc_pad), jnp.dtype(dtype)),
        sd((B, K, Pc_pad)), sd((B, K)), sd((B,), i32), sd((B,), i32)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = _record(record_property, compiled)
    # the new carry is the kernel's only output
    assert ma.output_size_in_bytes == B * K * Pc_pad * jnp.dtype(dtype).itemsize


def test_influence_kernel_compiles_for_v5e(one_chip, record_property):
    n, _, B = PHASES["B"]
    _, _, _, carry = _phase("B")
    P = carry["vals"].shape[-1]
    sd = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    compiled = kops.influence_update.lower(
        sd((B, n)), sd((B, n, n)), sd((B, n, P)), sd((B, n, P)),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _record(record_property, compiled)


@pytest.mark.parametrize("n", [16, 64, 512])
def test_tile_lookup_compiles_for_v5e_without_its_select_tensor(
        one_chip, record_property, n):
    """The J-hat tile lookup (K = n, batch 32) on both sides of the row
    width rule stores no 4-D select tensor (B n^3 floats, 16 GiB at
    n = 512, where selecting the rows as well made XLA store one); its
    temporaries stay within one [B, K, n] row block."""
    B = 32
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    idx = sd((B, n), jnp.int32)
    compiled = jax.jit(compact.gather_j_tiles).lower(
        sd((B, n, n)), idx, idx).compile()
    ma = _record(record_property, compiled)
    assert ma.temp_size_in_bytes <= B * n * n * 4


@pytest.mark.parametrize("phase", ["A", "B"])
def test_online_chunk_compiles_for_v5e(one_chip, kernel_path,
                                       record_property, phase):
    learner, opt, params, carry = _phase(phase)
    n, n_in, B = PHASES[phase]
    chunk = jax.jit(lambda c, o, xs, ys, upd: online_update_chunk(
        learner, opt, c, o, xs, ys, upd))
    compiled = chunk.lower(
        _on(one_chip, carry), _on(one_chip, jax.eval_shape(opt.init, params)),
        jax.ShapeDtypeStruct((K_UPDATE, B, n_in), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((K_UPDATE, B), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = _record(record_property, compiled)
    assert ma.temp_size_in_bytes < 16 * 2 ** 30        # one v5e's HBM


# granite-4.0-h-micro's top mixer as the benchmark cell holds it
SSM = {"B": 6, "Hl": 8, "P": 64, "N": 128, "D": 2048, "K": 4}
TRACE = "f32[6,8,64,128,2048]"


def _trace_fusions(text):
    """Instructions that produce a big trace: one fusion is one pass."""
    return [line for line in text.splitlines()
            if " = " in line and TRACE in line.split(" = ", 1)[1][:400]
            and "fusion(" in line]


def test_ssm_trace_update_compiles_for_v5e(one_chip, record_property):
    B, Hl, P, N, D, K = (SSM[k] for k in ("B", "Hl", "P", "N", "D", "K"))
    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    s = (B, Hl, P, N)
    tr = {"x": sd(*s, D), "B": sd(*s, D), "dt": sd(*s, D),
          "conv_x": sd(*s, K), "conv_x_b": sd(*s), "conv_B": sd(*s, K),
          "conv_B_b": sd(*s), "A_log": sd(*s), "dt_bias": sd(*s)}
    inc = dict(tr, x=(sd(*s), sd(B, Hl, P, D)), B=(sd(B, Hl, P), sd(B, N, D)),
               dt=(sd(*s), sd(B, D)))
    compiled = jax.jit(ssm_trace_update, donate_argnums=(0,)).lower(
        tr, sd(B, Hl), inc, sd(*s)).compile()
    ma = _record(record_property, compiled)
    big = 3 * B * Hl * P * N * D * 4
    assert ma.alias_size_in_bytes >= big           # updated in place
    assert ma.temp_size_in_bytes < big // 100
    assert len(_trace_fusions(compiled.as_text())) == 1


def test_granite_period_chunk_compiles_for_v5e(one_chip, record_property):
    from repro.cells.mamba2 import GraniteHybridConfig, init_params
    from repro.obs import MetricPack
    cfg = GraniteHybridConfig()
    B = SSM["B"]
    params, frozen = jax.eval_shape(lambda: init_params(cfg,
                                                        jax.random.key(0)))
    learner = make_learner(LearnerSpec(engine="diag_exact", cfg=cfg))
    opt = make_optimizer("adamw", lr=1e-4, b1=0.9, b2=0.95)
    carry = jax.eval_shape(lambda p, f: learner.init(
        p, None, (jnp.zeros((B, 2), jnp.int32), jnp.zeros((B,), jnp.int32)),
        t_total=K_UPDATE, frozen=f), params, frozen)
    pack = MetricPack.default()
    chunk = jax.jit(lambda c, o, xs, ys, upd: online_update_chunk(
        learner, opt, c, o, xs, ys, upd, pack=pack), donate_argnums=(0, 1))
    compiled = chunk.lower(
        _on(one_chip, carry), _on(one_chip, jax.eval_shape(opt.init, params)),
        jax.ShapeDtypeStruct((K_UPDATE, B, 2), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((K_UPDATE, B), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    ma = _record(record_property, compiled)
    held = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert ma.alias_size_in_bytes >= ma.argument_size_in_bytes - 2 ** 20
    assert held < 16e9                              # one v5e's HBM
    assert len(_trace_fusions(compiled.as_text())) == 1
