"""``prefill_attn_roofline`` on hand-built runs: the family's attention
work over the kept causal blocks of the finished prompts, over the
``attn`` programs' device time at the chip's bf16 peak."""
from types import SimpleNamespace as NS

import pytest

from _bench_path import ROOT  # noqa: F401  (puts bench on the path)
from bench.harness import Bench

BENCH = Bench()
READ = BENCH.reader("prefill_attn_roofline")
PEAKS = {"bf16_flops_per_s": 1.97e14}


def _run(prompts, densities, attn_s=None, module="attn",
         family="mla_moe", config="deepseek-v2-lite-ep8", block=128):
    done = [NS(state="done", prompt=[0] * n,
               pattern_stats=None if d is None else {"block_density": d})
            for n, d in zip(prompts, densities)]
    trace = None if attn_s is None else NS(module_s={module: attn_s,
                                                     "decode_step": 9.7})
    return NS(done=done, trace=trace, block=block, peaks=PEAKS,
              family=BENCH.family(family),
              sizes=BENCH.config(config)["model"])


def test_longdoc_backlog_by_hand():
    # six 32k prompts, dense: 256 blocks a side, 256 x 257 / 2 causal
    # blocks per head and layer; QK^T at 128 + 64 and PV at 128 wide
    blocks = 6 * 256 * 257 / 2
    flops = blocks * 16 * 27 * 2 * 128 * 128 * (128 + 64 + 128)
    got = READ(_run([32768] * 6, [1.0] * 6, attn_s=20.0))
    assert got == pytest.approx(100 * flops / (20.0 * 1.97e14))
    assert 0 < got < 100


def test_rag_backlog_by_hand():
    # six 8k prompts on the dense GQA family: 64 x 65 / 2 blocks, 4 x 128
    # operations per score over 16 heads and 24 layers
    flops = 6 * 64 * 65 / 2 * 16 * 24 * 4 * 128 * 128 * 128
    got = READ(_run([8192] * 6, [1.0] * 6, attn_s=0.5, family="dense_gqa",
                    config="internlm2-1.8b-dense"))
    assert got == pytest.approx(100 * flops / (0.5 * 1.97e14))


@pytest.mark.parametrize("density", [0.0, None])
def test_dense_run_counts_every_causal_block(density):
    full = READ(_run([32768, 8192], [1.0, 1.0], attn_s=10.0))
    assert READ(_run([32768, 8192], [density] * 2, attn_s=10.0)) == full
    assert READ(_run([32768, 8192], [0.5, 0.5], attn_s=10.0)) == \
        pytest.approx(full / 2)


def test_nothing_to_read():
    assert READ(_run([32768], [1.0])) is None                   # no trace
    assert READ(_run([32768], [1.0], attn_s=10.0,
                     module="decode_step_x")) is None           # no attn
    assert READ(_run([], [], attn_s=10.0)) is None              # no prompt
