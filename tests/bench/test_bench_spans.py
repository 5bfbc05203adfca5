"""The span reduction: host spans and device runs on one clock, on
hand-built events and on a recorded TPU trace."""
import json
import shutil
from pathlib import Path

import pytest

from _bench_path import ROOT
from bench import spans, xplane
from bench.harness import Bench

DATA = Path(__file__).parent / "data"
RECORDED = ("round", "launch", "wait", "sleep", "kernel")


def span(name, start, end, **args):
    return spans.Span(name, start, end, args)


# host clock, ns: one step holding two launches, their waits and a sleep;
# a third launch after the step, outside every span
HOST = spans.Host(
    [span("step", 0, 1000), span("launch", 90, 120),
     span("wait", 120, 250), span("sleep", 260, 290),
     span("launch", 295, 305), span("wait", 305, 400),
     span("decode_step", 2000, 2100, occupied=3),
     span("decode_step", 2200, 2300, occupied=1)],
    [100, 300, 1100])
# device clock: the runs start 50, 40 and 40 ns before their launches, so
# the offset is 50 and the runs lie at host [100, 200], [310, 360] and
# [1110, 1120]: idle [200, 310] and [360, 1110]
CHIP = xplane.Chip("/device:TPU:0", [], [("jit_a(1)", 50, 100),
                                        ("jit_b(2)", 260, 50),
                                        ("jit_c(3)", 1060, 10)])


def test_offset_and_attribution():
    s = spans.reduce(HOST, [CHIP])
    assert s.paired and s.offset_ns == 50
    assert s.launches == 3 and s.runs == [3]
    # each run to the innermost span open at its launch
    assert s.device_s == pytest.approx({"launch": 150e-9,
                                        "unattributed": 10e-9})
    # each idle interval split among the innermost spans open over it
    assert s.idle_s == pytest.approx({
        "wait": (50 + 5 + 40) * 1e-9, "step": (10 + 5 + 600) * 1e-9,
        "sleep": 30e-9, "launch": 10e-9, "unattributed": 110e-9})
    assert s.share(s.idle_s) == pytest.approx(1 - 110 / 860)
    assert s.share(s.device_s) == pytest.approx(150 / 160)
    assert s.count["launch"] == 2 and s.count["decode_step"] == 2
    assert s.device_ms("launch") == pytest.approx(75e-6)
    assert s.idle_ms(("wait", "sleep"), per="step") == pytest.approx(125e-6)
    assert s.mean_arg("decode_step", "occupied") == 2
    longest = s.longest("step")
    assert longest["s"] == pytest.approx(1e-6)
    assert longest["inside"] == pytest.approx(
        {"launch": 40e-9, "wait": 225e-9, "sleep": 30e-9})
    assert s.longest("sched_step") is None


def test_busy_follows_the_union_of_operations():
    # operations inside the first run leave a gap the module line hides
    chip = xplane.Chip("/device:TPU:0",
                       [("a", 50, 40), ("b", 110, 40), ("c", 260, 50),
                        ("d", 1060, 10)], CHIP.modules)
    s = spans.reduce(HOST, [chip])
    # host [140, 160] is idle inside "wait" on top of the module-line gaps
    assert s.idle_s["wait"] == pytest.approx((20 + 50 + 5 + 40) * 1e-9)


def test_counts_that_differ_pair_nothing():
    chip = xplane.Chip("/device:TPU:0", [], CHIP.modules[:2])
    s = spans.reduce(HOST, [chip])
    assert not s.paired and s.runs == [2] and s.launches == 3
    assert s.device_ms("launch") is None
    assert s.idle_ms(spans.DECODE, per="decode_step") is None
    assert s.share(s.idle_s) is None
    # what the host spans say alone still reads
    assert s.mean_arg("decode_step", "occupied") == 2
    assert spans.report(s) == {"launches": 3, "runs": [2],
                               "decode_batch_mean": 2.0}


def test_pieces_follow_the_innermost_span():
    p = spans.pieces([span("a", 0, 10), span("b", 2, 4), span("c", 4, 6),
                      span("d", 20, 30)])
    assert [(s, e, i) for s, e, i in p[1:-1]] == [
        (0, 2, 0), (2, 4, 1), (4, 6, 2), (6, 10, 0), (10, 20, -1),
        (20, 30, 3)]
    assert p[0][2] == p[-1][2] == -1


def test_span_names_are_the_programs():
    from repro.serving import tracing
    assert spans.SPANS == tracing.SPANS
    assert set(spans.ADMISSION + spans.DECODE) <= set(spans.SPANS)


def test_report():
    f = spans.report(spans.reduce(HOST, [CHIP]))
    assert f["offset_ms"] == pytest.approx(5e-5, abs=1e-4)
    assert f["idle_attributed"] == round(1 - 110 / 860, 4)
    assert f["busy_attributed"] == round(150 / 160, 4)
    assert list(f["idle_by_span"])[0] == "step"
    assert "longest_step" not in f
    assert json.loads(json.dumps(f)) == f


@pytest.mark.parametrize("name,value", [
    ("prefill_quantum_ms", None), ("kv_insert_ms", None),
    ("admission_host_gap_ms", None), ("decode_host_gap_ms", 0.0),
    ("decode_batch_mean", 2.0)])
def test_quantities(name, value):
    assert spans.quantities(spans.reduce(HOST, [CHIP]))[name] == value
    # nothing paired: only what the host spans say alone reads
    unpaired = spans.reduce(HOST, [xplane.Chip("/device:TPU:0", [], [])])
    assert spans.quantities(unpaired)[name] == (
        2.0 if name == "decode_batch_mean" else None)


def test_report_of_a_trace_file(capsys):
    # the recording's spans are not the program's: everything pairs and
    # nothing is attributed
    assert spans.main([str(DATA / "tpu_spans.xplane.pb")]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["launches"] == 6 and out["runs"] == [6]
    assert 0 < out["offset_ms"] < 5
    assert list(out["idle_by_span"]) == ["unattributed"]
    assert out["busy_attributed"] == out["idle_attributed"] == 0.0


def test_serve_a_cell_reports_its_spans(tmp_path):
    # the harness's tiny CPU cell: no chip plane, so nothing pairs, but the
    # host spans, the phases and the stall record read
    home = tmp_path / "bench"
    shutil.copytree(DATA / "home", home)
    shutil.copytree(ROOT / "bench" / "metrics", home / "metrics")
    shutil.copy(ROOT / "bench" / "peaks.json", home / "peaks.json")
    bench = Bench(home / "spec.json", home)
    out = spans.serve(bench, "tiny.tinymix", 4, 2.0, trace=True)
    assert out["runs"] == [] and "offset_ms" not in out
    assert out["longest_step"]["s"] > 0
    assert out["decode_batch_mean"] >= 1
    assert set(out["phase_s"]) == {"prefill", "decode", "idle", "refresh"}
    assert out["slowest_step"]["s"] > 0 and out["slowest_step"]["phase"]
    assert out["window_s"] > 0
    untraced = spans.serve(bench, "tiny.tinymix", 4, 2.0, trace=False)
    assert set(untraced) == {"window_s", "phase_s", "slowest_step"}


def test_recorded_trace():
    path = str(DATA / "tpu_spans.xplane.pb")
    host = spans.load(path, RECORDED)
    chips = xplane.load(path)
    assert [c.name for c in chips] == ["/device:TPU:0"]
    s = spans.reduce(host, chips)
    # three rounds of a matmul and a Pallas kernel: launches and runs pair
    assert s.paired and s.launches == 6 and s.runs == [6]
    assert s.count == {"round": 3, "launch": 3, "wait": 3, "sleep": 3,
                       "kernel": 3}
    # the device clock reads about a millisecond behind the host's
    assert 0 < s.offset_ns < 5e6
    # the runs start at or after their launches, each inside its span
    assert s.share(s.device_s) == 1.0
    assert set(s.device_s) == {"launch", "kernel"}
    # each 10 ms host sleep is device idle time charged to its span
    assert 3 * 9e-3 < s.idle_s["sleep"] < 3 * 12e-3
    assert max(s.idle_s, key=s.idle_s.get) == "sleep"
    assert s.share(s.idle_s) > 0.95


def test_first_recording_pairs_on_its_own():
    # the recording of test_bench_xplane holds no spans: everything pairs,
    # and every idle second is unattributed
    path = str(DATA / "tpu_small.xplane.pb")
    s = spans.reduce(spans.load(path), xplane.load(path))
    assert s.paired and s.launches == 6 and s.count == {}
    assert 1.1e6 < s.offset_ns < 1.4e6
    assert set(s.idle_s) == set(s.device_s) == {"unattributed"}
    assert s.share(s.idle_s) == 0.0
