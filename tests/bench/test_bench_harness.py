"""The harness finds a cell's configuration, family, mix, limits and
metrics by name, and refuses to run anywhere but on a TPU."""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _bench_path import ROOT
from bench import check, weights, work
from bench.harness import Bench, Session, model_config

# A family that is no dense GQA decoder, added as a file: a dense first
# layer kept apart from the stack (``prefix_0/``, as the program names
# one) and stacked experts, their axis after the layer axis.  Every token
# goes through every expert and there is no attention; its work counts are
# made up, so that the test sees which of them is used.
TOY_FAMILY = '''
import jax
import jax.numpy as jnp
import numpy as np
from repro.configs.base import MoEConfig
from bench.reference import mm


def model_fields(conf):
    m = conf["model"]
    return {"num_layers": 1 + m["layers"], "d_model": m["width"],
            "vocab_size": m["vocab"],
            "moe": MoEConfig(num_experts=m["experts"], top_k=m["experts"])}


def layout(sizes):
    d, e, n = sizes["width"], sizes["experts"], sizes["layers"]
    return {"embed": (sizes["vocab"], d), "prefix_0/mlp/w": (d, d),
            "prefix_0/norm/scale": (d,), "stack/experts/w": (n, e, d, d),
            "stack/norm/scale": (n, d), "head": (d, sizes["vocab"])}


def fan_in(path, shape):
    return 1 if path == "embed" else shape[-2]


def logits(params, sizes, tokens, rows, *, precision="f32", pad=1024,
           block=512):
    fp8 = precision == "fp8"
    p = {k: v.astype(jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        x = p["embed"][jnp.asarray(tokens)]
        x = x + jnp.tanh(mm("td,de->te", x, p["prefix_0/mlp/w"], fp8))
        for li in range(sizes["layers"]):
            x = x + jnp.tanh(mm("td,xde->te", x, p["stack/experts/w"][li],
                                fp8)) / sizes["experts"]
        return np.asarray(mm("td,dv->tv", x[jnp.asarray(rows)], p["head"],
                             fp8))


def matmul_params(sizes):
    d, e, n = sizes["width"], sizes["experts"], sizes["layers"]
    return {"layers": d * d + n * e * d * d, "head": d * sizes["vocab"]}


def attention_flops(blocks, block, sizes):
    return blocks * block * block * 3.0


def key_flops(sizes):
    return 5.0
'''
TOY = {"family": "toy_moe", "registry": "deepseek-v2-236b",
       "dtype": "float32", "engine": {},
       "model": {"layers": 2, "width": 16, "experts": 4, "vocab": 64}}


@pytest.fixture
def home(tmp_path):
    """A benchmark of one new cell and one new family, added as files
    only."""
    h = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", h)
    (h / "configs" / "m.json").write_text(json.dumps(
        {"registry": "granite-3-2b", "dtype": "bfloat16", "engine": {},
         "model": {"num_hidden_layers": 3, "hidden_size": 2048}}))
    (h / "traffic" / "t.json").write_text(json.dumps({"rate_per_s": 1.5}))
    (h / "limits" / "m.t.json").write_text(json.dumps({"logit_gap": 0.5}))
    (h / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return run * 2\n")
    (h / "families" / "toy_moe.py").write_text(TOY_FAMILY)
    (h / "configs" / "toy.json").write_text(json.dumps(TOY))
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
    dense = home.family("dense_gqa")
    cfg = model_config(home.config("m"), dense)
    assert cfg.num_layers == 3 and cfg.num_heads == 32 and cfg.head_dim == 0
    full = model_config(Bench().config("internlm2-1.8b-dense"), dense)
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


def _tree(shapes):
    """The parameter tree that ``path -> shape`` describes, as
    ``jax.eval_shape`` of a program's init gives it."""
    tree = {}
    for path, shape in shapes.items():
        *outer, last = path.split("/")
        node = tree
        for k in outer:
            node = node.setdefault(k, {})
        node[last] = jax.ShapeDtypeStruct(shape, jnp.float32)
    return tree


def test_a_new_family_is_named_by_its_configuration_file(home):
    conf = home.config("toy")
    cfg = model_config(conf, home.family(conf["family"]))
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == (3, 16, 64)
    assert (cfg.moe.num_experts, cfg.moe.top_k) == (4, 4)
    assert cfg.dtype == "float32"


def test_a_new_familys_layout_is_filled_and_checked(home):
    conf = home.config("toy")
    toy, sizes = home.family(conf["family"]), conf["model"]
    filled = weights.fill(_tree(toy.layout(sizes)), toy, sizes, 5,
                          jnp.float32)
    experts = np.asarray(filled["stack"]["experts"]["w"])
    assert experts.shape == (2, 4, 16, 16)
    assert experts.std() == pytest.approx(16 ** -0.5, rel=0.1)
    assert np.all(np.asarray(filled["prefix_0"]["norm"]["scale"]) == 1)
    # the reference's weights are the program's, from the same seed
    ref = Session.reference_params(
        SimpleNamespace(family=toy, sizes=sizes, dtype=jnp.float32), 5)
    assert np.array_equal(ref["stack/experts/w"], experts)
    assert np.array_equal(ref["prefix_0/mlp/w"],
                          filled["prefix_0"]["mlp"]["w"])
    # a tree the family's layout does not know is refused: a dense GQA
    # decoder's, or the toy's without its prefix layer
    tiny = json.loads((ROOT / "tests/bench/data/home/configs/tiny.json"
                       ).read_text())["model"]
    dense = home.family("dense_gqa")
    with pytest.raises(ValueError):
        weights.fill(_tree(dense.layout(tiny)), toy, sizes, 5)
    shapes = toy.layout(sizes)
    del shapes["prefix_0/mlp/w"]
    with pytest.raises(ValueError):
        weights.fill(_tree(shapes), toy, sizes, 5)


def test_a_new_family_sets_the_check_and_the_work_counts(home):
    conf = home.config("toy")
    toy, sizes = home.family(conf["family"]), conf["model"]
    params = weights.make(toy, sizes, 7, jnp.float32)
    prompt = np.arange(10) % 64
    first = toy.logits(params, sizes, prompt, np.asarray([9])).argmax(-1)
    seq = np.concatenate([prompt, first])
    ref = toy.logits(params, sizes, seq, np.asarray([9, 10]))
    greedy = ref.argmax(-1).tolist()
    good = SimpleNamespace(prompt=prompt, output_tokens=greedy,
                           pattern_stats={"block_density": 0.5},
                           plan_traffic_fraction=0.0)
    gaps = check.reference_gaps(toy, params, sizes, [good])
    assert gaps["logit_gap"] == 0.0 and gaps["tokens"] == 2
    altered = [greedy[0], (greedy[1] + 1) % 64]
    bad = SimpleNamespace(prompt=prompt, output_tokens=altered)
    assert check.reference_gaps(toy, params, sizes, [bad])["logit_gap"] == \
        pytest.approx(float(ref[1].max() - ref[1][altered[1]]))
    # prefill: the layers per prompt token, the head once, and half of one
    # 64-token block's pairs at 3; decode: one step's layers and head, and
    # its 11 keys at 5
    layers, head = 16 * 16 + 2 * 4 * 16 * 16, 16 * 64
    want = (2.0 * layers * 10 + 2.0 * head + 0.5 * 64 * 64 * 3.0
            + 2.0 * (layers + head) + 5.0 * 11)
    assert work.served_flops([good], 64, toy, sizes) == want
