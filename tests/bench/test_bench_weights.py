"""The benchmark's weight generator against the program's parameter tree."""
import json

import jax
import numpy as np
import pytest

from _bench_path import ROOT
from bench import weights
from bench.harness import Bench, model_config

DENSE = Bench().family("dense_gqa")

TINY = json.loads((ROOT / "tests/bench/data/home/configs/tiny.json"
                   ).read_text())


def _program_shape(conf):
    from repro.models import build_model
    model = build_model(model_config(conf, DENSE))
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


# granite-3.0-2b's published widths (head_dim 64, group 4, tied head): a
# second tree shape, though no cell serves it
GRANITE = {"registry": "granite-3-2b", "dtype": "bfloat16", "model": {
    "hidden_size": 2048, "intermediate_size": 8192,
    "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 64,
    "num_hidden_layers": 40, "vocab_size": 49155,
    "tie_word_embeddings": True}}


@pytest.mark.parametrize("name", ["internlm2-1.8b-dense", "granite-3-2b"])
def test_layout_is_the_programs_tree_at_full_size(name):
    conf = (GRANITE if name == "granite-3-2b" else json.loads(
        (ROOT / f"bench/configs/{name}.json").read_text()))
    got = {weights._path(kp): tuple(x.shape) for kp, x in
           jax.tree_util.tree_flatten_with_path(_program_shape(conf))[0]}
    assert got == DENSE.layout(conf["model"])


def test_fill_is_the_seeds_and_matches_make():
    shape = _program_shape(TINY)
    a = weights.fill(shape, DENSE, TINY["model"], 3)
    b = weights.make(DENSE, TINY["model"], 3)
    c = weights.fill(shape, DENSE, TINY["model"], 2**31 + 3)
    wq = np.asarray(a["stack"]["attn"]["wq"], np.float32)
    assert np.array_equal(wq, np.asarray(b["stack/attn/wq"], np.float32))
    assert not np.array_equal(
        wq, np.asarray(c["stack"]["attn"]["wq"], np.float32))
    assert a["embed"].dtype == jax.numpy.bfloat16
    # fan-in scaling: q projection std ~ hidden_size ** -0.5
    assert wq.std() == pytest.approx(64 ** -0.5, rel=0.1)
    assert np.all(np.asarray(a["final_norm"]["scale"]) == 1)


def test_a_tree_the_layout_does_not_know_is_refused():
    shape = _program_shape(TINY)
    shape["extra"] = jax.ShapeDtypeStruct((3,), jax.numpy.float32)
    with pytest.raises(ValueError):
        weights.fill(shape, DENSE, TINY["model"], 1)
