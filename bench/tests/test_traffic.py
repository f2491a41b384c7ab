"""The traffic generator: the same seed gives the same streams and the same
leavers, and every seed offers the same work (the same number of sessions
leaving, and so joining, after every window, and the same input shapes),
only with other sessions."""
import numpy as np
import pytest

from bench import run as R
from bench.traffic import generator as G

SEEDS = (1, 2 ** 31 + 11, 2 ** 33 + 5)
SLOTS = 1024


def _traffic(seed):
    r = R.resolve("n16-fleet1024")
    return G.Traffic(r["mix"], seed, r["config"]["model"]), r


def _drain(t, windows):
    """A saturated fleet of SLOTS sessions driven for `windows` windows:
    (leavers per window, lengths of the sessions that left)."""
    live = [f"s{i}" for i in range(SLOTS)]
    joined = {sid: 0 for sid in live}
    nxt, counts, lengths = SLOTS, [], []
    for w in range(windows):
        gone = t.leavers(w, live)
        counts.append(len(gone))
        for sid in gone:
            lengths.append(w + 1 - joined.pop(sid))
            live[live.index(sid)] = f"s{nxt}"
            joined[f"s{nxt}"] = w + 1
            nxt += 1
    return counts, lengths


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_offers_the_same_session_lengths(seed):
    t, r = _traffic(seed)
    counts, lengths = _drain(t, 120)
    base, _ = _drain(_traffic(SEEDS[0])[0], 120)
    assert counts == base
    mean = float(r["mix"]["session_windows_mean"])
    # 1024 / 12 = 85.33 sessions leave after every window, 85 or 86
    assert set(counts) == {85, 86} and sum(counts) == 120 * SLOTS // 12
    # lengths are geometric with that mean (those still live are left out,
    # which shortens the sample a little)
    assert min(lengths) >= 1 and abs(np.mean(lengths) - mean) < 0.15 * mean


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_streams(seed):
    a, r = _traffic(seed)
    b, _ = _traffic(seed)
    B = r["config"]["model"]["batch"]
    for i in (0, 5, 4099):
        sa, sb = a.session(i), b.session(i)
        assert sa[0] == sb[0]
        xa, ya = G.window_inputs(sa[1], 0, 16)
        xb, yb = G.window_inputs(sb[1], 0, 16)
        assert xa.shape == (16, B, 2) and ya.shape == (16, B)
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
    live = [f"s{i}" for i in range(SLOTS)]
    assert a.leavers(7, live) == b.leavers(7, live)
