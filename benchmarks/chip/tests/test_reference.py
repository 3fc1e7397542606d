"""The plain reference against the program's ``model.forward`` on the same
seeded weights, at a small size on the CPU, in float32.  Both share only
the weights, which ``weights.py`` makes; the tolerance is float32 rounding
over two layers (sums in another order), far below any gap the benchmark
compares."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import reference, weights


def _tiny(arch):
    from repro.configs.base import get_config
    return dataclasses.replace(get_config(arch).reduced(), dtype="float32")


@pytest.mark.parametrize("arch", ["smollm-360m", "internlm2-1.8b"])
def test_tree_matches_the_program(arch):
    """``to_program`` gives the tree ``model.init_params`` gives, leaf for
    leaf in shape and dtype."""
    from repro.models import model
    from repro.models.layers import split_params
    cfg = dataclasses.replace(_tiny(arch), dtype="bfloat16")
    want = jax.eval_shape(lambda k: split_params(model.init_params(k, cfg))[0],
                          jax.random.PRNGKey(0))
    s = weights.Shape.of(cfg)
    got = jax.eval_shape(lambda: weights.to_program(weights._make(
        jax.random.PRNGKey(0), s), s))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.mark.parametrize("arch", ["smollm-360m", "internlm2-1.8b"])
def test_reference_matches_model_forward(arch):
    from repro.models import model
    cfg = _tiny(arch)
    s = weights.Shape.of(cfg)
    w = weights.make(s, 2**31 + 12345)
    T = 40
    tokens = np.random.default_rng(0).integers(0, s.vocab, T).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = model.forward(weights.to_program(w, s), cfg,
                             {"tokens": jnp.asarray(tokens)[None]})[0]
    want = np.asarray(want[0, :, :s.vocab])
    pos = np.arange(T)
    got = reference.logits_at(s, w, tokens, pos, length=64, n_pos=T)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # padding past the sequence changes nothing before it
    got_long = reference.logits_at(s, w, tokens, pos, length=128, n_pos=48)
    np.testing.assert_allclose(got_long, got, rtol=1e-5, atol=1e-5)


def test_gaps():
    ref = np.array([[1.0, 3.0, 2.0], [0.5, 0.0, -1.0]])
    np.testing.assert_allclose(reference.gaps(ref, [1, 2]), [0.0, 1.5])


def test_fp8_control_differs_from_reference():
    cfg = _tiny("smollm-360m")
    s = weights.Shape.of(cfg)
    w = weights.make(s, 5)
    tokens = np.arange(30, dtype=np.int32)
    pos = np.arange(30)
    ref = reference.logits_at(s, w, tokens, pos, 32, 30)
    ctl = reference.logits_at(s, w, tokens, pos, 32, 30, fp8=True)
    err = np.abs(ctl - ref).max()
    assert 1e-3 < err < 10 * np.abs(ref).max()
