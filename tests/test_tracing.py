"""The scheduler's spans: a chunked paged serve under the profiler leaves
one span per unit of work on the host plane, with the counts and ids the
benchmark's trace reader (``bench/spans.py``) relies on, and the phase
accounting keeps its keys."""
import glob
from collections import Counter, defaultdict

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.data import DataConfig, sample
from repro.models import build_model
from repro.serving import EngineConfig, Request, ServingEngine, tracing

CFG = get_smoke_config("granite-3-2b")
SEQ = 256
BS = CFG.share_prefill.block_size       # 64: one block per attn quantum
MAX_NEW = (5, 2, 4)
PHASES = {"prefill", "decode", "idle", "refresh"}
CHUNKED = {"sched_step", "reap", "assemble_run", "idle_sleep",
           "prefill_quantum", "kv_insert", "complete_run", "decode_step",
           "decode_args", "decode_launch", "decode_wait", "sample"}


def parse_uids(value):
    """The uids of a ``uid`` argument: ``4``, or ``"u4+u5"`` for a packed
    run (``tracing.uid_arg``)."""
    if isinstance(value, str):
        return [int(u[1:]) for u in value.split("+")]
    return [value]


def host_spans(trace_dir):
    """``(name, start_ns, end_ns, args)`` of every scheduler span."""
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in tracing.SPANS:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def model():
    m = build_model(CFG)
    return m, m.init(jax.random.PRNGKey(0)), m.default_share_prefill()


@pytest.mark.parametrize("pack", [1, 2], ids=["solo", "packed"])
def test_chunked_paged_serve_spans(model, tmp_path, pack):
    m, params, sp = model
    eng = ServingEngine(m, params, sp, EngineConfig(
        method="share", max_batch=2, seq_buckets=(SEQ,), paged=True,
        prefill_chunk=BS, prefill_pack=pack))
    dcfg = DataConfig(vocab_size=CFG.vocab_size, seq_len=SEQ,
                      global_batch=1, task="retrieval")
    # every request is due a little after the serve starts, so the
    # scheduler's first step sleeps for it with every slot idle
    reqs = [Request(uid=10 + i, prompt=sample(dcfg, i)["tokens"],
                    max_new_tokens=n, arrival_s=0.3)
            for i, n in enumerate(MAX_NEW)]
    with jax.profiler.trace(str(tmp_path)):
        eng.serve(reqs)
    assert all(len(r.output_tokens) == r.max_new_tokens for r in reqs)
    spans = host_spans(tmp_path)
    by_name = defaultdict(list)
    for s in spans:
        by_name[s[0]].append(s)
    assert set(by_name) == CHUNKED

    # admission: one run per distinct uid argument, each with its quanta,
    # one K/V insert per layer and one completion; every request in one run
    L = CFG.num_layers
    runs = Counter(s[3]["uid"] for s in by_name["prefill_quantum"])
    uids = [u for run in runs for u in parse_uids(run)]
    assert sorted(uids) == [r.uid for r in reqs]
    assert any(isinstance(run, str) for run in runs) == (pack > 1)
    for run, n in runs.items():
        P = len(parse_uids(run))
        attn = P * SEQ // BS
        assert n == 2 + L * (2 + attn)      # ChunkedPrefillRun.quanta_total
        kinds = Counter(s[3]["kind"] for s in by_name["prefill_quantum"]
                        if s[3]["uid"] == run)
        assert kinds == {"begin": 1, "layer_begin": L, "attn": L * attn,
                         "layer_end": L, "finish": 1}
        inserts = [s[3] for s in by_name["kv_insert"] if s[3]["uid"] == run]
        assert sorted(a["layer"] for a in inserts) == list(range(L))
        assert all(a["pages"] == P * SEQ // BS for a in inserts)
        assert [s[3]["uid"] for s in by_name["complete_run"]].count(run) == 1
    assert len(by_name["kv_insert"]) == L * len(runs)

    # decode: one span per step, its occupied slots are the tokens decoded
    steps = by_name["decode_step"]
    assert len(steps) == eng.slot_steps // eng.ecfg.max_batch
    assert sum(s[3]["occupied"] for s in steps) == eng.active_slot_steps \
        == sum(r.max_new_tokens - 1 for r in reqs)
    for child in ("decode_args", "decode_launch", "decode_wait", "sample"):
        assert len(by_name[child]) == len(steps)

    # nesting: every quantum inside a scheduler step, numbered from 1
    sched = by_name["sched_step"]
    assert sorted(s[3]["step_num"] for s in sched) == \
        list(range(1, len(sched) + 1))
    for q in by_name["prefill_quantum"]:
        assert any(s[1] <= q[1] and q[2] <= s[2] for s in sched)

    assert set(eng.phase_s) == PHASES
    assert eng.phase_s["idle"] > 0.2 and eng.phase_s["prefill"] > 0
    slow = eng.slowest_step
    assert 1 <= slow["step"] <= len(sched)
    assert set(slow["phase_s"]) == PHASES | {"other"}
    assert slow["phase_s"][slow["phase"]] == max(slow["phase_s"].values())
    assert sum(slow["phase_s"].values()) == pytest.approx(slow["s"])


def test_serve_profile_dir(monkeypatch, tmp_path):
    """``launch/serve.py --profile-dir`` traces the serve, spans and all."""
    from repro.launch import serve as serve_cli
    monkeypatch.setattr(serve_cli, "enable_compile_cache", lambda: "")
    assert serve_cli.main([
        "--arch", "granite-3-2b", "--smoke", "--paged", "--num-requests",
        "2", "--prompt-len", "256", "--prefill-chunk", "64", "--max-new",
        "3", "--max-batch", "2", "--profile-dir", str(tmp_path)]) == 0
    names = Counter(s[0] for s in host_spans(tmp_path))
    assert names["prefill_quantum"] == 2 * (2 + CFG.num_layers * (2 + 4))
    assert names["kv_insert"] == 2 * CFG.num_layers
    assert names["complete_run"] == 2
    assert names["decode_step"] >= 2
