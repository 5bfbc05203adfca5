"""Work counts against hand arithmetic for internlm2-1.8b at 32k."""
import json

import pytest

from _bench_path import ROOT
from bench import work

SIZES = json.loads((ROOT / "bench/configs/internlm2-1.8b-dense.json").read_text()
                   )["model"]


def test_matmul_params_by_hand():
    p = work.matmul_params(SIZES)
    # per layer: q 2048x16x128, k and v 2048x8x128 each, o 16x128x2048,
    # gate/up/down 3x2048x8192
    per_layer = (2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
                 + 3 * 2048 * 8192)
    assert p["layers"] == 24 * per_layer == 1_509_949_440
    assert p["head"] == 2048 * 92544


def test_prefill_at_32k_by_hand():
    n, bs, density = 32768, 128, 0.85
    nb = 256
    kept = density * nb * (nb + 1) / 2 * 16 * 24
    assert work.kept_blocks(density, n, bs, SIZES) == pytest.approx(kept)
    assert work.block_flops(bs, SIZES) == 4 * 128 * 128 * 128
    linear = 2 * 1_509_949_440 * n + 2 * 2048 * 92544
    attn = kept * 4 * 128 * 128 * 128
    assert work.prefill_flops(n, density, bs, SIZES) == pytest.approx(
        linear + attn)
    # the issue's reckoning: ~1.0e14 linear, ~0.9e14 attention at 0.85
    assert 0.9e14 < linear < 1.1e14 and 0.8e14 < attn < 1.0e14


def test_decode_by_hand():
    f = work.decode_flops(1000, 3, 0.5, SIZES)
    per_tok = 2 * (1_509_949_440 + 2048 * 92544)
    ctx = 1001 + 1002
    assert f == pytest.approx(2 * per_tok + 4 * 128 * 16 * 24 * ctx * 0.5)
    assert work.decode_flops(1000, 1, 0.5, SIZES) == 0.0


def test_served_flops_sums_each_request_at_its_own_counters():
    from types import SimpleNamespace as R
    reqs = [R(prompt=[0] * 4096, output_tokens=[1] * 9,
              pattern_stats={"block_density": 0.5},
              plan_traffic_fraction=0.25),
            R(prompt=[0] * 256, output_tokens=[1], pattern_stats=None,
              plan_traffic_fraction=0.0)]
    want = (work.prefill_flops(4096, 0.5, 128, SIZES)
            + work.decode_flops(4096, 9, 0.25, SIZES)
            + work.prefill_flops(256, 1.0, 128, SIZES))
    assert work.served_flops(reqs, 128, SIZES) == pytest.approx(want)
    assert work.served_flops([], 128, SIZES) == 0.0
