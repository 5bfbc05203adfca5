"""The benchmark's open-loop traffic generator."""
import numpy as np
import pytest

from _bench_path import ROOT  # noqa: F401
from bench import traffic

MIX = {"rate_per_s": 0.5,
       "prompt_tokens": {"dist": "log_uniform", "lo": 6144, "hi": 30720},
       "output_tokens": {"dist": "log_uniform", "lo": 32, "hi": 256}}


def test_ladder_is_the_quantiles_of_the_distribution():
    lad = traffic.ladder({"dist": "log_uniform", "lo": 64, "hi": 512}, 4)
    want = [64 * 8 ** ((i + 0.5) / 4) for i in range(4)]
    assert lad.tolist() == [round(w) for w in want]
    with pytest.raises(ValueError):
        traffic.ladder({"dist": "zipf", "lo": 1, "hi": 2}, 3)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, -3])
def test_every_seed_offers_the_same_mix_of_lengths(seed):
    base = traffic.offers(MIX, 40, 1, 1000)
    got = traffic.offers(MIX, 40, seed, 1000)
    assert len(got) == len(base) == 20
    assert sorted(len(o.prompt) for o in got) == \
        sorted(len(o.prompt) for o in base)
    assert sorted(o.max_new for o in got) == sorted(o.max_new for o in base)
    gaps = np.diff([o.arrival_s for o in got])
    assert (gaps > 0).all() and got[0].arrival_s == 0.0
    # the gaps between arrivals are all but one of the exponential ladder
    ladder = traffic.gap_ladder(MIX["rate_per_s"], 20)
    assert all(np.isclose(ladder, g).sum() == 1 for g in gaps)


def test_seed_changes_content_not_schedule():
    a = traffic.offers(MIX, 40, 1, 1000)
    b = traffic.offers(MIX, 40, 2**31 + 5, 1000)
    assert [(len(o.prompt), o.max_new, o.arrival_s) for o in a] == \
        [(len(o.prompt), o.max_new, o.arrival_s) for o in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # the schedule pairs the ladders in a mixed order, not ladder by ladder
    assert [len(o.prompt) for o in a] != sorted(len(o.prompt) for o in a)
    again = traffic.offers(MIX, 40, 1, 1000)
    assert all(np.array_equal(x.prompt, y.prompt) and x.arrival_s ==
               y.arrival_s for x, y in zip(a, again))


def test_warmup_covers_each_bucket_once_from_its_own_stream():
    warm = traffic.warmup_offers(MIX, 40, 1000, (512, 2048, 8192, 32768))
    assert [len(o.prompt) for o in warm] == \
        [max(n for n in traffic.ladder(MIX["prompt_tokens"], 20) if n <= b)
         for b in (8192, 32768)]
    assert all(o.arrival_s == 0.0 and o.max_new == 2 for o in warm)
    runs = traffic.offers(MIX, 40, 0, 1000)
    assert not any(np.array_equal(w.prompt[:64], r.prompt[:64])
                   for w in warm for r in runs)


def test_backlog_is_due_at_once_with_fixed_prompts():
    mix = dict(MIX, arrivals="backlog",
               prompt_tokens={"dist": "fixed", "tokens": 8192})
    got = traffic.offers(mix, 40, 2**31 + 9, 1000)
    assert len(got) == 20
    assert all(o.arrival_s == 0.0 and len(o.prompt) == 8192 for o in got)
    assert sorted(o.max_new for o in got) == \
        sorted(traffic.ladder(MIX["output_tokens"], 20).tolist())
    with pytest.raises(ValueError):
        traffic.offers(dict(MIX, arrivals="bursty"), 40, 1, 1000)
