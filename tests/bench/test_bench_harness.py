"""The harness finds a cell's configuration, mix, limits and metrics by
name, and refuses to run anywhere but on a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from _bench_path import ROOT
from bench.harness import Bench, model_config


@pytest.fixture
def home(tmp_path):
    """A benchmark of one new cell, added as files only."""
    h = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", h)
    (h / "configs" / "m.json").write_text(json.dumps(
        {"registry": "granite-3-2b", "dtype": "bfloat16", "engine": {},
         "model": {"num_hidden_layers": 3, "hidden_size": 2048}}))
    (h / "traffic" / "t.json").write_text(json.dumps({"rate_per_s": 1.5}))
    (h / "limits" / "m.t.json").write_text(json.dumps({"logit_gap": 0.5}))
    (h / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return run * 2\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "m.t", "config": "m", "traffic": "t",
                              "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("m.t")
    spec["per_layer"].append({"name": "new_metric", "unit": "count",
                              "better": "lower", "source": "program_counter",
                              "layer": "engine",
                              "moves": "output_tokens_per_s"})
    p = tmp_path / "BENCHMARK.json"
    p.write_text(json.dumps(spec))
    return Bench(p, h)


def test_finds_config_mix_limits_and_metric_by_name(home):
    cell = home.cell("m.t")
    assert home.config(cell["config"])["model"]["num_hidden_layers"] == 3
    assert home.mix(cell["traffic"])["rate_per_s"] == 1.5
    assert home.limits("m.t")["logit_gap"] == 0.5
    assert home.reader("new_metric")(21) == 42
    with pytest.raises(KeyError):
        home.cell("nope")


def test_metric_selection_by_cell(home):
    e2e = {m["name"] for m in home.metrics("m.t", trace=False)}
    assert e2e == {"output_tokens_per_s", "setup_s"}
    per = {m["name"] for m in home.metrics("m.t", trace=True)}
    # a metric without a workloads key follows the end-to-end metric it
    # moves; the others list their cells
    assert per == {"new_metric"}
    rag = {m["name"] for m in home.metrics("internlm2-1.8b-dense.rag8k",
                                            True)}
    assert {"step_mfu.throughput", "decode_step_ms", "new_metric"} <= rag
    home.spec["end_to_end"][0]["workloads"].remove("m.t")
    assert {m["name"] for m in home.metrics("m.t", trace=False)} == \
        {"setup_s"}


def test_every_metric_and_cell_has_its_files():
    b = Bench()
    for m in b.spec["end_to_end"] + b.spec["per_layer"]:
        assert callable(b.reader(m["name"]))
    for c in b.spec["workloads"]:
        assert b.config(c["config"])["name"] == c["config"]
        assert "rate_per_s" in b.mix(c["traffic"])
        assert b.limits(c["name"])["logit_gap"] > 0


def test_configuration_sizes_reach_the_program(home):
    cfg = model_config(home.config("m"))
    assert cfg.num_layers == 3 and cfg.num_heads == 32 and cfg.head_dim == 0
    full = model_config(Bench().config("internlm2-1.8b-dense"))
    assert (full.num_layers, full.num_heads, full.resolved_head_dim,
            full.num_kv_heads, full.vocab_size, full.tie_embeddings) == \
        (24, 16, 128, 8, 92544, False)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        Bench().peaks("TPU v99")
    assert Bench().peaks("TPU v5 lite")["bf16_flops_per_s"] == 1.97e14


def test_run_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "internlm2-1.8b-dense.rag8k", "--seed", "1", "--seconds", "1"],
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
