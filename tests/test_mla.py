"""YaRN rope and the mscale softmax scale of multi-head latent attention
against hand-computed values for DeepSeek-V2-Lite's published numbers
(rotary dim 64, θ 10,000, factor 40, 4,096 original positions, β_fast
32, β_slow 1, mscale = mscale_all_dim = 0.707)."""
import math

import numpy as np
import pytest

from repro.configs import get_config
from repro.models import common, mla


def test_yarn_frequencies_by_hand():
    # correction dims: 64 ln(4096 / (32 · 2π)) / (2 ln 10^4) = 10.47 -> 10,
    # 64 ln(4096 / (1 · 2π)) / (2 ln 10^4) = 22.51 -> 23; ramp (i-10)/13
    inv = np.asarray(common.yarn_frequencies(64, 10000.0, 40.0, 4096,
                                             32.0, 1.0))
    assert inv.shape == (32,)
    assert inv[0] == pytest.approx(1.0)
    assert inv[9] == pytest.approx(10000 ** (-18 / 64), rel=1e-6)
    assert inv[10] == pytest.approx(0.0562341325, rel=1e-6)
    # 0.01 · (7 + 6/40) / 13
    assert inv[16] == pytest.approx(0.0055, rel=1e-6)
    assert inv[23] == pytest.approx(10000 ** (-46 / 64) / 40, rel=1e-6)
    assert inv[31] == pytest.approx(3.33380358e-06, rel=1e-6)


def test_mscale_and_softmax_scale_by_hand():
    cfg = get_config("deepseek-v2-lite")
    # 0.1 · 0.707 · ln 40 + 1
    assert common.yarn_mscale(40.0, 0.707) == pytest.approx(1.2608037774)
    assert common.yarn_mscale(1.0, 0.707) == 1.0
    # mscale² / √(128 + 64)
    assert mla.softmax_scale(cfg) == pytest.approx(0.1147213868)
    assert mla.q_temperature(cfg) == pytest.approx(1.2608037774 ** 2)
    plain = get_config("deepseek-v2-236b")
    assert mla.softmax_scale(plain) == pytest.approx(1 / math.sqrt(192))
