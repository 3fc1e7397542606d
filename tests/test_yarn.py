"""YaRN as DeepSeek-V2 publishes it: rotary frequencies and the attention
scale, against values written here from the published formulas
(``DeepseekV2YarnRotaryEmbedding`` and ``yarn_get_mscale``)."""
import math

import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.models.attention import mla_softmax_scale
from repro.models.layers import (apply_rope, rope_freqs,
                                 yarn_correction_range, yarn_softmax_scale)

CFG = get_config("deepseek-v2-lite-16b")
YARN = CFG.rope_yarn


def _published_inv_freq(dim=64, base=10000.0, factor=40.0, orig=4096,
                        beta_fast=32.0, beta_slow=1.0):
    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / \
            (2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra, inter = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask, (low, high)


def test_yarn_config_is_the_published_one():
    assert (YARN.factor, YARN.original_max_position, YARN.beta_fast,
            YARN.beta_slow, YARN.mscale, YARN.mscale_all_dim) == \
        (40, 4096, 32, 1, 0.707, 0.707)
    assert CFG.kv_norm and CFG.qk_rope_dim == 64


def test_yarn_frequencies_match_the_published_formula():
    want, (low, high) = _published_inv_freq()
    assert yarn_correction_range(YARN, 64, 10000.0) == (low, high) == (10, 23)
    got = np.asarray(rope_freqs(64, 10000.0, YARN), np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    plain = np.asarray(rope_freqs(64, 10000.0), np.float64)
    # fast dims keep their frequency, slow dims are interpolated by 40
    np.testing.assert_allclose(got[:low], plain[:low], rtol=1e-7)
    np.testing.assert_allclose(got[high:], plain[high:] / 40, rtol=1e-6)


def test_yarn_softmax_scale_is_published():
    m = 0.1 * 0.707 * math.log(40) + 1.0
    assert math.isclose(yarn_softmax_scale(YARN), m * m, rel_tol=1e-12)
    assert math.isclose(m * m, 1.5896, abs_tol=1e-4)
    assert math.isclose(mla_softmax_scale(CFG), 192 ** -0.5 * m * m,
                        rel_tol=1e-12)


def test_yarn_rope_rotates_with_unit_mscale():
    """mscale == mscale_all_dim: cos/sin are not scaled, so the rotation
    keeps each pair's norm; position 0 is the identity."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 5, 2, 64)),
                    jnp.float32)
    pos = jnp.arange(5)[None]
    y = apply_rope(x, pos, 10000.0, YARN)
    n = lambda a: np.hypot(np.asarray(a[..., :32]), np.asarray(a[..., 32:]))
    np.testing.assert_allclose(n(y), n(x), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]),
                               rtol=1e-6)
    assert not np.allclose(np.asarray(y), np.asarray(apply_rope(x, pos,
                                                                10000.0)))
