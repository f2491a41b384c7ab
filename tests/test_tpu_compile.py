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
    fused kernel (`tpu_custom_call`).

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
from repro.kernels import compact_fused as CF
from repro.kernels import ops as kops
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
