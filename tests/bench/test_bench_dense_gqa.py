"""The dense GQA family gives the bits the benchmark gave before its
layout, fan-in rule and reference moved into ``bench/families``: the
weights of a seed to the bit, and the reference's logits at the tiny size
as ``data/dense_gqa_pin.json`` holds them.

The logits are held to 1e-5, not to the bit: XLA's CPU backend orders a
contraction's sums by the cores it finds, which moves them by a few ulps
(7e-7 at most here, between one core and eight), while a change of the
computation moves them by tenths."""
import hashlib
import json

import numpy as np
import pytest

from _bench_path import ROOT
from bench import weights
from bench.harness import Bench

PIN = json.loads((ROOT / "tests/bench/data/dense_gqa_pin.json").read_text())
TINY = json.loads((ROOT / "tests/bench/data/home/configs/tiny.json"
                   ).read_text())["model"]
DENSE = Bench().family("dense_gqa")


@pytest.mark.parametrize("seed", [3, 2**31 + 3])
def test_weights_are_the_bits_of_before(seed):
    p = weights.make(DENSE, TINY, seed)
    h = hashlib.sha256()
    for k in sorted(p):
        h.update(k.encode())
        h.update(np.asarray(p[k]).tobytes())
    assert h.hexdigest() == PIN["weights_sha256"][str(seed)]


@pytest.mark.parametrize("precision", ["f32", "fp8"])
def test_reference_logits_are_those_of_before(precision):
    p = weights.make(DENSE, TINY, PIN["seed"])
    tokens = np.random.default_rng(0).integers(0, 2048, 300)
    got = DENSE.logits(p, TINY, tokens, np.asarray(PIN["rows"]),
                       precision=precision, pad=PIN["pad"],
                       block=PIN["block"])
    want = PIN[precision]
    assert got.argmax(1).tolist() == want["argmax"]
    np.testing.assert_allclose(got.max(1), want["max"], rtol=0, atol=1e-5)
    np.testing.assert_allclose(got[:, PIN["cols"]], want["at_cols"], rtol=0,
                               atol=1e-5)
