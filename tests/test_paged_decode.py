"""Paged decode-attention: Pallas kernel vs the page-loop jnp oracle
(bit-exact in interpret mode), the oracle vs the dense decode oracle, the
pool-packing helper's layout invariants, and ops dispatch."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.paged_decode import paged_decode_attention, pack_kv_pools

KEY = jax.random.PRNGKey(11)


def make_case(B, S, H, KVH, D, page, dtype=jnp.float32, cold_frac=0.5):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kc = jax.random.normal(ks[1], (B, S, KVH, D), dtype)
    vc = jax.random.normal(ks[2], (B, S, KVH, D), dtype)
    lengths = jnp.array([S - 1 - (5 * b) % (S // 2) for b in range(B)],
                        jnp.int32)
    cold = [int(int(l) * cold_frac) for l in lengths]
    pools = pack_kv_pools(kc, vc, cold, page)
    return q, kc, vc, lengths, pools


@pytest.mark.parametrize("B,S,H,KVH,D,page", [
    (2, 64, 4, 2, 16, 8),
    (3, 128, 8, 4, 32, 16),
    (1, 32, 2, 1, 128, 8),       # MQA, page smaller than D
    (2, 96, 6, 2, 64, 16),       # non-power-of-two heads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_bit_exact_vs_oracle(B, S, H, KVH, D, page, dtype):
    """The kernel and the oracle run the same op sequence (shared
    masked_scores/online_softmax_update), so interpret mode must agree
    bit-for-bit, not merely to tolerance."""
    q, _, _, lengths, (kh, vh, kc, vc, tab, tier) = make_case(
        B, S, H, KVH, D, page, dtype)
    out = paged_decode_attention(q, kh, vh, kc, vc, tab, tier, lengths,
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, kh, vh, kc, vc, tab, tier,
                                          lengths)
    assert out.dtype == q.dtype
    assert jnp.array_equal(out, want)


@pytest.mark.parametrize("window,cap", [(0, 0.0), (24, 0.0), (0, 30.0),
                                        (16, 50.0)])
def test_paged_kernel_window_softcap_bit_exact(window, cap):
    q, _, _, lengths, (kh, vh, kc, vc, tab, tier) = make_case(
        2, 96, 4, 2, 32, 16)
    out = paged_decode_attention(q, kh, vh, kc, vc, tab, tier, lengths,
                                 window=window, softcap_val=cap,
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, kh, vh, kc, vc, tab, tier,
                                          lengths, window=window,
                                          softcap_val=cap)
    assert jnp.array_equal(out, want)


@pytest.mark.parametrize("window,cap", [(0, 0.0), (24, 0.0), (8, 30.0)])
def test_paged_oracle_matches_dense_decode(window, cap):
    """Paging (and the hot/cold split) is a layout change only: the paged
    oracle agrees with the dense decode oracle to float tolerance."""
    q, kc_d, vc_d, lengths, (kh, vh, kc, vc, tab, tier) = make_case(
        2, 64, 4, 2, 32, 8)
    out = ref.paged_decode_attention_ref(q, kh, vh, kc, vc, tab, tier,
                                         lengths, window=window,
                                         softcap_val=cap)
    want = ref.decode_attention_ref(q, kc_d, vc_d, lengths, window=window,
                                    softcap_val=cap)
    assert jnp.max(jnp.abs(out - want)) < 1e-4


def test_pack_kv_pools_layout_invariants():
    """Physical ids are unique within a tier, tiers form a per-slot cold
    prefix, and gathering pages back through the table reconstructs the
    dense cache exactly."""
    B, S, KVH, D, page = 3, 64, 2, 16, 8
    ks = jax.random.split(KEY, 2)
    kc = jax.random.normal(ks[0], (B, S, KVH, D))
    vc = jax.random.normal(ks[1], (B, S, KVH, D))
    cold = [16, 0, 40]
    kh, vh, kcold, vcold, tab, tier = pack_kv_pools(kc, vc, cold, page)
    NP = S // page
    for t in (0, 1):
        ids = [int(tab[b, i]) for b in range(B) for i in range(NP)
               if int(tier[b, i]) == t]
        assert len(ids) == len(set(ids))
    for b in range(B):
        n_cold = cold[b] // page
        assert [int(x) for x in tier[b]] == [1] * n_cold + [0] * (NP - n_cold)
    # reconstruct
    for b in range(B):
        for i in range(NP):
            pool = kcold if int(tier[b, i]) else kh      # (n, KVH, page, D)
            assert jnp.array_equal(pool[int(tab[b, i])],
                                   kc[b, i * page:(i + 1) * page]
                                   .transpose(1, 0, 2))


def test_ops_dispatch_paged():
    """ops.paged_decode_attention: jnp oracle on CPU by default; forced
    Pallas path (interpret) returns the identical array."""
    q, _, _, lengths, (kh, vh, kc, vc, tab, tier) = make_case(
        2, 64, 4, 2, 16, 8)
    want = ref.paged_decode_attention_ref(q, kh, vh, kc, vc, tab, tier,
                                          lengths)
    out = ops.paged_decode_attention(q, kh, vh, kc, vc, tab, tier, lengths)
    assert jnp.array_equal(out, want)
    ops.use_pallas(True)
    try:
        out_pl = ops.paged_decode_attention(q, kh, vh, kc, vc, tab, tier,
                                            lengths)
    finally:
        ops.use_pallas(False)
    assert jnp.array_equal(out_pl, want)
