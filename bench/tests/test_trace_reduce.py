"""The trace reduction: interval arithmetic and gap attribution on
intervals worked out by hand."""
import pytest

from bench import trace_reduce as TR


def test_union_merges_overlaps_and_touching_intervals():
    assert TR.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 12)]) == \
        [[0, 4], [5, 7], [10, 12]]


def test_covered_clips_to_the_window():
    merged = [[0, 4], [5, 7], [10, 12]]
    assert TR.covered(merged, 0, 12) == 8
    assert TR.covered(merged, 3, 11) == 1 + 2 + 1


def test_gaps_go_to_the_innermost_span_of_the_window_thread():
    busy = [[0, 100_000], [150_000, 300_000], [300_005, 400_000]]
    host = {"/host:CPU/main": [(0, 400_000, "window"),
                               (100_000, 140_000, "admit"),
                               (110_000, 120_000, "write")],
            "/host:CPU/other": [(0, 400_000, "elsewhere")]}
    gaps = TR._gaps(busy, 0, 400_000, host)
    # [100000, 150000): midpoint 125000 lies in "admit" only (write ends
    # at 120000); [300000, 300005) is under 10 us
    assert gaps == pytest.approx({"admit": 50e-6,
                                  "(between ops, under 10 us)": 5e-9})


def test_reduce_by_hand():
    """Two windows of a made-up device timeline (ns): the chunk program
    "c" runs once per window span, a small program "w" between them."""
    ms = 1_000_000
    ops = [(1 * ms, 4 * ms, "fusion.1", "c"), (4 * ms, 6 * ms, "kernel", "c"),
           (9 * ms, 10 * ms, "copy", "w"),
           (12 * ms, 15 * ms, "fusion.1", "c"),
           (15 * ms + 5_000, 17 * ms, "kernel", "c")]
    host = {"/host:CPU/main": [(0, 7 * ms, "window"),
                               (7 * ms, 11 * ms, "admit"),
                               (11 * ms, 18 * ms, "window")]}
    t = TR.reduce({"device": "/device:TPU:0", "ops": ops, "host": host})
    assert t["window_s"] == pytest.approx(18e-3)
    # busy: 1-6, 9-10, 12-15, 15.005-17 ms
    assert t["busy_s"] == pytest.approx(10.995e-3)
    chunk = t["chunk"]
    assert chunk["program"] == "c" and chunk["runs"] == 2
    assert chunk["busy_s"] == pytest.approx(9.995e-3)
    # between 6 and 12 ms: 6 ms, of which 1 ms busy with "w"
    assert chunk["idle_between_s"] == pytest.approx(5e-3)
    assert t["ops"] == pytest.approx({"fusion.1": 6e-3, "kernel": 3.995e-3,
                                      "copy": 1e-3})
    # 0-1, 10-12 and 17-18 ms under "window", 6-9 ms under "admit", and
    # the 5 us between the two kernel-side ops lumped
    assert t["gaps"] == pytest.approx({"window": 4e-3, "admit": 3e-3,
                                       "(between ops, under 10 us)": 5e-6})
    assert TR.breakdown(t)["device_ops"][0] == ["fusion.1", t["ops"]["fusion.1"]]


def test_nested_ops_count_their_own_time():
    """A loop's op event holds its body's ops: each counts its own time."""
    ms = 1_000_000
    ops = [(0, 10 * ms, "while.2", "c"), (1 * ms, 4 * ms, "fusion.1", "c"),
           (5 * ms, 9 * ms, "fusion.2", "c"), (6 * ms, 7 * ms, "copy", "c")]
    # while.2: 10 - 3 - 4; fusion.2: 4 - 1
    assert TR.self_times(ops) == [3 * ms, 3 * ms, 3 * ms, 1 * ms]
    host = {"/host:CPU/main": [(0, 10 * ms, "window")]}
    t = TR.reduce({"device": "/device:TPU:0", "ops": ops, "host": host})
    assert t["busy_s"] == pytest.approx(10e-3)
    assert t["ops"] == pytest.approx({"while.2": 3e-3, "fusion.1": 3e-3,
                                      "fusion.2": 3e-3, "copy": 1e-3})


def test_op_name_is_the_instruction():
    assert TR.op_name("%fusion.213 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.213"
    assert TR.op_name("custom-call") == "custom-call"


def test_recorded_fleet_trace():
    """Two windows of the fleet at 16 slots, recorded on a TPU v5e; the
    numbers were worked out once by painting every op onto a 1-ns timeline
    (innermost op wins) and counting."""
    t = TR.summarize(TR.Path(__file__).resolve().parents[1] / "testdata"
                     / "fleet16-2win.xplane.pb.gz")
    assert t["device"] == "/device:TPU:0"
    assert t["window_s"] == pytest.approx(0.084341291, rel=1e-12)
    assert t["busy_s"] == pytest.approx(0.044982994, rel=1e-12)
    chunk = t["chunk"]
    assert chunk["program"] == "jit__lambda(12878584767323155814)"
    assert chunk["runs"] == 2
    assert chunk["busy_s"] == pytest.approx(0.044832855, rel=1e-12)
    assert chunk["idle_between_s"] == pytest.approx(0.036703999, rel=1e-12)
    top = TR.breakdown(t)["device_ops"]
    assert [n for n, _ in top[:3]] == ["fusion.213", "fusion.201",
                                       "fusion.207"]
    assert top[0][1] == pytest.approx(0.026490725, rel=1e-12)
    assert top[1][1] == pytest.approx(0.011674261, rel=1e-12)
    # every idle nanosecond of the window is attributed to some host span
    assert sum(t["gaps"].values()) == pytest.approx(
        t["window_s"] - t["busy_s"], rel=1e-9)
