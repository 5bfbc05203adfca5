"""The comparison that decides ``correct``, driven through a whole run of a
CPU-sized cell (the harness's look for a chip skipped): a sound run is
correct, the float8 control fails the cell's limit, and a token altered
where the scheduler produces it makes the run not correct."""
import json
import shutil
import time

import numpy as np
import pytest

from _bench_path import ROOT
from bench.harness import Bench, run_cell

DATA = ROOT / "tests" / "bench" / "data" / "home"
SEED = 4


@pytest.fixture
def tiny(tmp_path):
    home = tmp_path / "bench"
    shutil.copytree(DATA, home)
    shutil.copytree(ROOT / "bench" / "metrics", home / "metrics")
    shutil.copytree(ROOT / "bench" / "families", home / "families")
    shutil.copy(ROOT / "bench" / "peaks.json", home / "peaks.json")
    return Bench(home / "spec.json", home)


def _run(bench):
    return run_cell(bench, "tiny.tinymix", SEED, 2.0, False,
                    t_start=time.perf_counter())


def test_sound_run_is_correct(tiny):
    out = _run(tiny)
    assert out["correct"] is True
    assert out["attempted"] == 4 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert out["check"]["logit_gap"]["value"] <= 0.06
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert out["metrics"]["output_tokens_per_s"]["value"] > 0
    assert json.loads(json.dumps(out)) == out


def test_altered_token_is_not_correct(tiny, monkeypatch):
    from repro.serving import scheduler

    step = scheduler.SlotScheduler._decode_step

    def altered(self):
        step(self)
        for s in self.slots:
            if s is not None and len(s.outs) == 3:
                s.outs[-1] = (s.outs[-1] + 1) % 2048
                s.last_tok = s.outs[-1]
                return

    monkeypatch.setattr(scheduler.SlotScheduler, "_decode_step", altered)
    out = _run(tiny)
    assert out["correct"] is False
    assert out["check"]["logit_gap"]["value"] > 0.06


def test_float8_control_fails_the_limit(tiny, monkeypatch):
    """The reference in float8 put in the program's place: each finished
    request's served tokens are replaced by the float8 reference's greedy
    tokens from the same prompt, and the run's own comparison finds the
    run not correct."""
    from bench.harness import Session
    serve = Session.serve

    def float8_served(self, seed, seconds, trace=False, rate=0.0):
        run = serve(self, seed, seconds, trace, rate)
        params = self.reference_params(seed)
        for r in run.done:
            seq = list(r.prompt)
            for _ in range(len(r.output_tokens)):
                low = self.family.logits(params, self.sizes, np.asarray(seq),
                                         np.asarray([len(seq) - 1]), pad=512,
                                         precision="fp8")
                seq.append(int(low[0].argmax()))
            r.output_tokens = seq[len(r.prompt):]
        return run

    monkeypatch.setattr(Session, "serve", float8_served)
    out = _run(tiny)
    assert out["correct"] is False
    assert out["check"]["logit_gap"]["value"] > \
        out["check"]["logit_gap"]["limit"]
