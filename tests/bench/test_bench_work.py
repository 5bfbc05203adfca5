"""Work counts against hand arithmetic for internlm2-1.8b at 32k."""
import json

import pytest

from _bench_path import ROOT
from bench import work
from bench.harness import Bench

SIZES = json.loads((ROOT / "bench/configs/internlm2-1.8b-dense.json"
                    ).read_text())["model"]
DENSE = Bench().family("dense_gqa")


def test_matmul_params_by_hand():
    p = DENSE.matmul_params(SIZES)
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
    assert work.kept_blocks(density, n, bs) * 16 * 24 == pytest.approx(kept)
    assert DENSE.attention_flops(1, bs, SIZES) == 16 * 24 * 4 * 128 * 128 * 128
    linear = 2 * 1_509_949_440 * n + 2 * 2048 * 92544
    attn = kept * 4 * 128 * 128 * 128
    assert work.prefill_flops(n, density, bs, DENSE, SIZES) == pytest.approx(
        linear + attn)
    # the issue's reckoning: ~1.0e14 linear, ~0.9e14 attention at 0.85
    assert 0.9e14 < linear < 1.1e14 and 0.8e14 < attn < 1.0e14


def test_decode_by_hand():
    f = work.decode_flops(1000, 3, 0.5, DENSE, SIZES)
    per_tok = 2 * (1_509_949_440 + 2048 * 92544)
    ctx = 1001 + 1002
    assert f == pytest.approx(2 * per_tok + 4 * 128 * 16 * 24 * ctx * 0.5)
    assert work.decode_flops(1000, 1, 0.5, DENSE, SIZES) == 0.0


def test_served_flops_sums_each_request_at_its_own_counters():
    from types import SimpleNamespace as R
    reqs = [R(prompt=[0] * 4096, output_tokens=[1] * 9,
              pattern_stats={"block_density": 0.5},
              plan_traffic_fraction=0.25),
            R(prompt=[0] * 256, output_tokens=[1], pattern_stats=None,
              plan_traffic_fraction=0.0)]
    want = (work.prefill_flops(4096, 0.5, 128, DENSE, SIZES)
            + work.decode_flops(4096, 9, 0.25, DENSE, SIZES)
            + work.prefill_flops(256, 1.0, 128, DENSE, SIZES))
    assert work.served_flops(reqs, 128, DENSE, SIZES) == pytest.approx(want)
    assert work.served_flops([], 128, DENSE, SIZES) == 0.0


# served_flops of the code before the counts moved behind the family: the
# rag8k backlog (6 x 8,192 tokens, dense), two requests at fractional
# density and traffic, and one at 20 heads, where the products round
# differently in another order; step_mfu.throughput prints these digits
# over a trace
TWENTY_HEADS = dict(SIZES, hidden_size=3072, num_attention_heads=20,
                    num_key_value_heads=4, num_hidden_layers=27,
                    vocab_size=102400)


@pytest.mark.parametrize("shape,want", [
    ("rag8k", 189644948373504.0),
    ("sparse", 205059522217475.34),
    ("twenty_heads", 197474385384505.97),
])
def test_served_flops_are_the_digits_of_before(shape, want):
    from types import SimpleNamespace as R
    sizes = SIZES
    if shape == "rag8k":
        reqs = [R(prompt=[0] * 8192, output_tokens=[1] * n,
                  pattern_stats=None, plan_traffic_fraction=0.0)
                for n in (18, 23, 28, 36, 45, 57)]
    elif shape == "sparse":
        reqs = [R(prompt=[0] * 32768, output_tokens=[1] * 9,
                  pattern_stats={"block_density": 0.8537},
                  plan_traffic_fraction=0.3141),
                R(prompt=[0] * 5000, output_tokens=[1] * 40,
                  pattern_stats={"block_density": 0.1234567},
                  plan_traffic_fraction=0.0)]
    else:
        sizes = TWENTY_HEADS
        reqs = [R(prompt=[0] * 29444, output_tokens=[1] * 17,
                  pattern_stats={"block_density": 0.3899367},
                  plan_traffic_fraction=0.0151)]
    assert work.served_flops(reqs, 128, DENSE, sizes) == want
