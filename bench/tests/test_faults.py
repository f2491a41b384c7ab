"""The faults `correct` has to catch, each planted underneath the timed
path on the CPU at a tiny size, the rest of the run as it is: a step that
returns its state unchanged, half of the batch left out of the loss's mean,
and a window's answer (its loss) altered where it is produced.  Each cell
runs on one chip, so no exchange between chips can be left out.  Where a
cell checks a window restarted from the state its span left, the first two
fail that window's numbers too."""
import pytest

from bench.tests.rehearse import run_tiny

CELLS = ("n16-fleet1024", "n16-stream")


def _unchanged_state(monkeypatch):
    """The update chunk returns the state it was given."""
    import repro.runtime.fleet as fleet
    import repro.runtime.online as online
    orig = online.online_update_chunk

    def broken(learner, opt, carry, opt_state, *a, **kw):
        _, _, metrics = orig(learner, opt, carry, opt_state, *a, **kw)
        return carry, opt_state, metrics
    monkeypatch.setattr(online, "online_update_chunk", broken)
    monkeypatch.setattr(fleet, "online_update_chunk", broken)


def _half_batch(monkeypatch):
    """The loss is the mean over the first half of each batch only."""
    import jax.numpy as jnp

    import repro.core.cells as cells

    def xent(logits, labels):
        h = labels.shape[0] // 2
        lp = jnp.take_along_axis(
            __import__("jax").nn.log_softmax(logits[:h], -1),
            labels[:h, None], axis=1)
        return -jnp.mean(lp)
    monkeypatch.setattr(cells, "xent", xent)


def _altered_answer(monkeypatch):
    """The window loss that the chunk hands back is altered."""
    import repro.runtime.fleet as fleet
    import repro.runtime.online as online
    orig = online.online_update_chunk

    def broken(*a, **kw):
        carry, opt_state, metrics = orig(*a, **kw)
        if "packed" in metrics:       # the trainer's one packed readback
            from repro.obs import MetricPack
            i = MetricPack.default().names.index("loss")
            metrics = dict(metrics,
                           packed=metrics["packed"].at[i].multiply(1.01))
        else:
            metrics = dict(metrics, loss=metrics["loss"] * 1.01)
        return carry, opt_state, metrics
    monkeypatch.setattr(online, "online_update_chunk", broken)
    monkeypatch.setattr(fleet, "online_update_chunk", broken)


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_makes_correct_false(workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run_tiny(workload)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_fault_fails_the_restarted_window(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run_tiny("n16-stream")
    restart = {k: c for k, c in out["checks"].items()
               if k.startswith("restart.")}
    assert restart and any(c["value"] > c["limit"]
                           for c in restart.values()), out["checks"]
