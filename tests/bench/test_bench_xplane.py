"""The trace reduction, on hand-built events and on a recorded TPU trace."""
from pathlib import Path

import pytest

from _bench_path import ROOT  # noqa: F401
from bench import xplane

DATA = Path(__file__).parent / "data"


def test_busy_is_the_union_of_op_intervals():
    ops = [("%a = f32[] a()", 0, 10), ("%b.3 = f32[] b()", 5, 10),
           ("%c.1.2 = f32[] c()", 30, 5), ("%d = f32[] d()", 100, 10)]
    mods = [("jit_first(1)", 0, 16), ("jit_second(2)", 30, 5),
            ("jit_third(3)", 100, 10)]
    s = xplane.reduce([xplane.Chip("/device:TPU:0", ops, mods)],
                      window_s=200e-9)
    assert s.busy_s == pytest.approx(30e-9)
    assert s.idle_share == pytest.approx(1 - 30 / 200)
    assert s.op_s == pytest.approx({"first:a": 10e-9, "first:b": 10e-9,
                                    "second:c": 5e-9, "third:d": 10e-9})
    assert s.module_s == pytest.approx({"first": 16e-9, "second": 5e-9,
                                        "third": 10e-9})
    assert s.module_n == {"first": 1, "second": 1, "third": 1}
    assert s.gaps[0] == ("after second", pytest.approx(65e-9))
    assert s.gaps[1] == ("after first", pytest.approx(15e-9))
    b = xplane.breakdown(s)
    assert b["device_ops"][0] == ["first", pytest.approx(16e-9)]
    assert len(b["idle_gaps"]) == 2


def test_busy_is_averaged_over_chips():
    c0 = xplane.Chip("/device:TPU:0", [("a", 0, 10)], [])
    c1 = xplane.Chip("/device:TPU:1", [("a", 0, 30)], [])
    s = xplane.reduce([c0, c1], window_s=100e-9)
    assert s.busy_s == pytest.approx(20e-9) and s.chips == 2


def test_no_tpu_plane_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce([], 1.0)


def test_recorded_trace():
    path = DATA / "tpu_small.xplane.pb"
    chips = xplane.load(str(path))
    assert [c.name for c in chips] == ["/device:TPU:0"]
    s = xplane.reduce(chips, window_s=1.0)
    # hand-checked against the file: the two programs ran three times each
    assert sorted(s.module_s) == ["add_one", "matmul"]
    merged = xplane.union([(st, st + d) for _, st, d in chips[0].ops])
    assert s.busy_s == pytest.approx(sum(e - b for b, e in merged) * 1e-9)
    assert 0 < s.busy_s < 0.03
    assert s.gaps[0][1] > 0.005           # the 10 ms host sleeps
    # the Pallas kernel is named after its function, inside its program
    assert s.op_s["add_one:add_one"] == pytest.approx(
        sum(d for n, _, d in chips[0].ops if "add_one" in n) * 1e-9)
    assert s.module_s["matmul"] > s.module_s["add_one"]
    assert s.module_n == {"matmul": 3, "add_one": 3}
