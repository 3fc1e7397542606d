"""The held-expert layer (models/moe.py::held_moe): dropless routing with
the published gate, the experts this shard holds and nothing else, and the
``held_experts`` kernel against its oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels import ops, ref
from repro.kernels.held_experts import held_experts
from repro.models import moe
from repro.models.layers import mlp, split_params

E, K, T = 8, 2, 12


def _cfg(**moe_kw):
    base = get_config("deepseek-v2-lite-16b").reduced()
    return dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, num_experts=E, experts_per_token=K, **moe_kw))


def _params(cfg, seed=0):
    p, _ = split_params(moe.init_moe(jax.random.PRNGKey(seed), cfg))
    return p


def _x(cfg, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, T // 2,
                                                        cfg.d_model))


def _manual(params, cfg, x, normalize):
    """Per token: softmax over all experts, top-k, each chosen held
    expert's SwiGLU times its weight; plus the shared experts."""
    m = cfg.moe
    xt = np.asarray(x.reshape(-1, cfg.d_model), np.float64)
    logits = xt @ np.asarray(params["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(xt)
    silu = lambda a: a / (1 + np.exp(-a))
    for t in range(xt.shape[0]):
        top = np.argsort(-probs[t])[:m.experts_per_token]
        w = probs[t, top] / (probs[t, top].sum() if normalize else 1.0)
        for e, wt in zip(top, w):
            j = e - m.first_expert
            if 0 <= j < m.held:
                g = silu(xt[t] @ np.asarray(params["wg"][j], np.float64))
                u = xt[t] @ np.asarray(params["wi"][j], np.float64)
                out[t] += wt * ((g * u) @ np.asarray(params["wo"][j],
                                                     np.float64))
    shared = np.asarray(mlp(params["shared"], x, cfg.act), np.float64)
    return out.reshape(x.shape) + shared


def test_route_topk_renormalisation_is_the_configs_choice():
    logits = jax.random.normal(jax.random.PRNGKey(0), (5, E))
    probs = jax.nn.softmax(logits, axis=-1)
    w_norm, i_norm = moe.route_topk(logits, K, True)
    w_pub, i_pub = moe.route_topk(logits, K, False)
    assert jnp.array_equal(i_norm, i_pub)
    np.testing.assert_allclose(w_norm.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        w_pub, jnp.take_along_axis(probs, i_pub, axis=-1), rtol=1e-6)
    assert bool((w_pub.sum(-1) < 1.0).all())


@pytest.mark.parametrize("normalize", [False, True])
def test_held_layer_is_the_published_gate_over_held_experts(normalize):
    cfg = _cfg(norm_topk_prob=normalize, first_expert=2, num_held=3)
    params, x = _params(cfg), _x(cfg)
    assert params["router"].shape[-1] == E and params["wg"].shape[0] == 3
    out, aux = moe.moe_mlp(params, cfg, x, cfg.act)
    np.testing.assert_allclose(np.asarray(out),
                               _manual(params, cfg, x, normalize),
                               rtol=2e-4, atol=2e-5)
    assert np.isfinite(float(aux))


def test_dropless_under_skewed_routing():
    """Every token routed to experts 0 and 1: the capacity dispatch drops
    most of them, the held-expert layer computes every one."""
    cfg = _cfg(norm_topk_prob=False)
    params = _params(cfg)
    bias = jnp.zeros((cfg.d_model, E)).at[:, :2].set(1.0)
    params["router"] = params["router"] * 0.01 + bias
    x = jnp.abs(_x(cfg)) + 0.5                # positive: experts 0, 1 win
    _, idx = moe.route_topk(
        x.reshape(-1, cfg.d_model) @ params["router"], K, False)
    assert set(np.asarray(idx).ravel()) == {0, 1}
    want = _manual(params, cfg, x, False)
    out, _ = moe.moe_mlp(params, cfg, x, cfg.act)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)
    capped = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, dropless=False, capacity_factor=1.0))
    dropped, _ = moe.moe_mlp(params, capped, x, cfg.act)
    assert not np.allclose(np.asarray(dropped), want, rtol=1e-2, atol=1e-3)


def test_shares_add_up_to_the_uncut_layer():
    """Four shards of two experts each: their parts, with the shared
    experts counted once, are the whole layer's output."""
    whole = _cfg()
    params, x = _params(whole), _x(whole)
    want, _ = moe.moe_mlp(params, whole, x, whole.act)
    shared = mlp(params["shared"], x, whole.act)
    total = shared
    for first in range(0, E, 2):
        cut = dataclasses.replace(whole, moe=dataclasses.replace(
            whole.moe, first_expert=first, num_held=2))
        part = dict(params, wg=params["wg"][first:first + 2],
                    wi=params["wi"][first:first + 2],
                    wo=params["wo"][first:first + 2])
        out, _ = moe.moe_mlp(part, cut, x, whole.act)
        total = total + (out - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows", [5, 300])
def test_held_experts_kernel_matches_its_oracle(dtype, rows):
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    d, F, H = 64, 256, 3
    x = jax.random.normal(k[0], (rows, d)).astype(dtype)
    comb = jax.random.uniform(k[1], (rows, H))
    wg = (jax.random.normal(k[2], (H, d, F)) * d ** -0.5).astype(dtype)
    wi = (jax.random.normal(k[3], (H, d, F)) * d ** -0.5).astype(dtype)
    wo = (jax.random.normal(k[4], (H, F, d)) * F ** -0.5).astype(dtype)
    got = held_experts(x, comb, wg, wi, wo, interpret=True)
    want = ref.held_experts_ref(x, comb, wg, wi, wo)
    assert got.shape == (rows, d) and got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


def test_held_experts_gradient_is_the_oracles(monkeypatch):
    monkeypatch.setitem(ops._FORCE, "pallas", True)
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    args = (jax.random.normal(k[0], (6, 16)), jax.random.uniform(k[1], (6, 2)),
            jax.random.normal(k[2], (2, 16, 8)),
            jax.random.normal(k[3], (2, 16, 8)),
            jax.random.normal(k[4], (2, 8, 16)))
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) ** 2)
    got = jax.grad(loss(ops.held_experts), argnums=(0, 1, 2))(*args)
    want = jax.grad(loss(ref.held_experts_ref), argnums=(0, 1, 2))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5)
