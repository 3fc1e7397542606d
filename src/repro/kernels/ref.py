"""Pure-jnp oracles for every Pallas kernel (and the CPU execution path).

These are the semantics of record: each kernel in this package must match its
oracle to float tolerance across shape/dtype sweeps (tests/test_kernels_*).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 20


# ------------------------------------------------------- flash attention ----

def attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                  softcap_val: float = 0.0, scale: float | None = None):
    """q: (B, Sq, H, D); k, v: (B, Skv, KVH, D) with H % KVH == 0.

    Plain softmax attention; the oracle for flash_attention.
    """
    B, Sq, H, D = q.shape
    KVH = k.shape[2]
    G = H // KVH
    scale = D ** -0.5 if scale is None else scale
    qg = q.reshape(B, Sq, KVH, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap_val:
        s = softcap_val * jnp.tanh(s / softcap_val)
    qpos = jnp.arange(Sq)[:, None] + (k.shape[1] - Sq)  # right-aligned
    kpos = jnp.arange(k.shape[1])[None, :]
    ok = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        ok &= kpos <= qpos
    if window:
        ok &= (qpos - kpos) < window
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return o.reshape(B, Sq, H, D)


def decode_attention_ref(q, k_cache, v_cache, lengths, *, window: int = 0,
                         softcap_val: float = 0.0):
    """Single-token decode. q: (B, H, D); caches: (B, S, KVH, D);
    lengths: (B,) int32 — #valid cache entries (query is at lengths-1)."""
    B, H, D = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    qg = q.reshape(B, KVH, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * (D ** -0.5)
    if softcap_val:
        s = softcap_val * jnp.tanh(s / softcap_val)
    kpos = jnp.arange(S)[None, :]
    ok = kpos < lengths[:, None]
    if window:
        ok &= kpos >= (lengths[:, None] - window)
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, H, D)


def paged_decode_attention_ref(q, k_hot, v_hot, k_cold, v_cold, page_table,
                               page_tier, lengths, *, window: int = 0,
                               softcap_val: float = 0.0):
    """Oracle for kernels/paged_decode.py: the same flash-decode page loop in
    pure jnp, vectorized over (batch, kv_head).

    q: (B, H, D); pools (n, KVH, page, D); page_table/page_tier (B, NP);
    lengths (B,).  The loop visits every logical page and relies on exact
    float semantics for tier-agnostic correctness: a fully masked page scores
    NEG_INF everywhere, whose exp underflows to exactly 0.0 in float32, so
    out-of-range pages (and, under ``window``, the skipped cold prefix)
    contribute nothing bit-for-bit.  The op sequence mirrors the kernel
    (shared masked_scores / online_softmax_update helpers), which is what
    makes the kernel-vs-oracle tests in interpret mode exact rather than
    approximate.
    """
    from repro.kernels.decode_attention import (masked_scores,
                                                online_softmax_update)
    B, H, D = q.shape
    KVH, page = k_hot.shape[1], k_hot.shape[2]
    NP = page_table.shape[1]
    G = H // KVH
    qg = q.reshape(B, KVH, G, D).astype(jnp.float32)
    lengths = lengths.astype(jnp.int32)

    def gather(pool_hot, pool_cold, i):
        phys = page_table[:, i]
        t = page_tier[:, i]
        hot = pool_hot[jnp.clip(phys, 0, pool_hot.shape[0] - 1)]
        cold = pool_cold[jnp.clip(phys, 0, pool_cold.shape[0] - 1)]
        pg = jnp.where(t[:, None, None, None] == 0, hot, cold)  # (B,KVH,page,D)
        return pg.astype(jnp.float32)

    def body(i, carry):
        acc, m, l = carry
        k = gather(k_hot, k_cold, i)
        v = gather(v_hot, v_cold, i)
        s = masked_scores(qg, k, i * page, lengths, window=window,
                          softcap_val=softcap_val)
        return online_softmax_update(s, v, acc, m, l)

    acc, m, l = jax.lax.fori_loop(
        0, NP, body,
        (jnp.zeros((B, KVH, G, D), jnp.float32),
         jnp.full((B, KVH, G), NEG_INF, jnp.float32),
         jnp.zeros((B, KVH, G), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype).reshape(B, H, D)


def latent_decode_attention_ref(q_lat, q_rope, ckv_hot, kr_hot, ckv_cold,
                                kr_cold, page_table, page_tier, lengths, *,
                                scale: float):
    """Oracle for kernels/paged_decode.py::latent_decode_attention: the same
    page loop in pure jnp, vectorized over the batch (see
    ``paged_decode_attention_ref`` for why out-of-range pages contribute
    exactly nothing).

    q_lat: (B, H, C) queries absorbed into the latent; q_rope: (B, H, R);
    pools ckv (n, page, C), kr (n, page, R); page_table / page_tier
    (B, NP); lengths (B,).  Returns (B, H, C)."""
    from repro.kernels.paged_decode import latent_scores
    from repro.kernels.decode_attention import (NEG_INF,
                                                online_softmax_update)
    B, H, C = q_lat.shape
    page = ckv_hot.shape[1]
    NP = page_table.shape[1]
    ql = q_lat.astype(jnp.float32)
    qr = q_rope.astype(jnp.float32)
    lengths = lengths.astype(jnp.int32)

    def gather(pool_hot, pool_cold, i):
        phys = page_table[:, i]
        hot = pool_hot[jnp.clip(phys, 0, pool_hot.shape[0] - 1)]
        cold = pool_cold[jnp.clip(phys, 0, pool_cold.shape[0] - 1)]
        pg = jnp.where(page_tier[:, i][:, None, None] == 0, hot, cold)
        return pg.astype(jnp.float32)                     # (B, page, ·)

    def body(i, carry):
        acc, m, l = carry
        ckv = gather(ckv_hot, ckv_cold, i)
        kr = gather(kr_hot, kr_cold, i)
        s = latent_scores(ql, qr, ckv, kr, i * page, lengths, scale)
        return online_softmax_update(s, ckv, acc, m, l)

    acc, m, l = jax.lax.fori_loop(
        0, NP, body,
        (jnp.zeros((B, H, C), jnp.float32),
         jnp.full((B, H), NEG_INF, jnp.float32),
         jnp.zeros((B, H), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q_lat.dtype)


# ---------------------------------------------------------- held experts ----

def held_experts_ref(x, comb, wg, wi, wo, *, act: str = "silu"):
    """Oracle for kernels/held_experts.py: x (T, d), comb (T, E), banks
    wg / wi (E, d, F), wo (E, F, d) -> (T, d) in x's dtype."""
    from repro.models.layers import act_fn
    f32 = jnp.float32
    g = jnp.einsum("td,edf->etf", x, wg, preferred_element_type=f32)
    u = jnp.einsum("td,edf->etf", x, wi, preferred_element_type=f32)
    h = (act_fn(act)(g) * u).astype(x.dtype)
    y = jnp.einsum("etf,efd->etd", h, wo, preferred_element_type=f32)
    return jnp.einsum("te,etd->td", comb.astype(f32), y).astype(x.dtype)


# ------------------------------------------------------------ mamba2 SSD ----

def ssd_ref(x, dt, A, Bm, Cm, *, h0=None):
    """Sequential oracle. x: (B,S,H,P); dt: (B,S,H); A: (H,) (negative);
    Bm, Cm: (B,S,N). Returns (y (B,S,H,P), h_final (B,H,P,N))."""
    B_, S, H, P = x.shape
    N = Bm.shape[-1]
    h = jnp.zeros((B_, H, P, N), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp  # (B,H,P), (B,H), (B,N), (B,N)
        a = jnp.exp(dtt * A)                                  # (B,H)
        h = h * a[..., None, None] + jnp.einsum(
            "bhp,bn,bh->bhpn", xt.astype(jnp.float32), bt.astype(jnp.float32), dtt)
        y = jnp.einsum("bhpn,bn->bhp", h, ct.astype(jnp.float32))
        return h, y

    xs = (x.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
          Bm.transpose(1, 0, 2), Cm.transpose(1, 0, 2))
    h, ys = jax.lax.scan(step, h, xs)
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), h


def ssd_chunked_ref(x, dt, A, Bm, Cm, *, chunk: int = 64, h0=None):
    """Chunked (SSD-algorithm) oracle — matmul-heavy formulation.

    Same I/O as ssd_ref; matches it to fp tolerance. This is the math the
    Pallas kernel implements per (batch, chunk) grid cell.
    """
    B_, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:  # dt=0 padding is neutral: decay 1, zero state contribution
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    S_p = S + pad
    K = S_p // Q
    xc = x.reshape(B_, K, Q, H, P).astype(jnp.float32)
    dtc = dt.reshape(B_, K, Q, H).astype(jnp.float32)
    Bc = Bm.reshape(B_, K, Q, N).astype(jnp.float32)
    Cc = Cm.reshape(B_, K, Q, N).astype(jnp.float32)

    logdec = dtc * A                                   # (B,K,Q,H), <= 0
    l = jnp.cumsum(logdec, axis=2)                     # inclusive
    total = l[:, :, -1, :]                             # (B,K,H)

    # intra-chunk: G[t,s] = (C_t . B_s) exp(l_t - l_s) dt_s, s <= t.
    # Mask the exponent BEFORE exp: for s > t the difference is positive and
    # can overflow, and a post-exp `where` still leaks inf into the VJP.
    CB = jnp.einsum("bktn,bksn->bkts", Cc, Bc)
    mask = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    diff = l[:, :, :, None, :] - l[:, :, None, :, :]            # (B,K,t,s,H)
    decay = jnp.exp(jnp.where(mask, diff, -jnp.inf))
    G = CB[..., None] * decay * dtc[:, :, None, :, :]
    y_intra = jnp.einsum("bktsh,bkshp->bkthp", G, xc)

    # inter-chunk via scan carrying h (B,H,P,N)
    h = jnp.zeros((B_, H, P, N), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def body(h, inp):
        xk, dtk, bk, ck, lk, tot = inp
        y_inter = jnp.einsum("btn,bhpn->bthp", ck, h) * jnp.exp(lk)[..., None]
        w = jnp.exp(tot[:, None, :] - lk) * dtk        # (B,Q,H)
        h = h * jnp.exp(tot)[:, :, None, None] + \
            jnp.einsum("bthp,btn,bth->bhpn", xk, bk, w)
        return h, y_inter

    xs = tuple(a.swapaxes(0, 1) for a in (xc, dtc, Bc, Cc, l, total))
    h, y_inter = jax.lax.scan(body, h, xs)
    y = (y_intra + y_inter.swapaxes(0, 1)).reshape(B_, S_p, H, P)[:, :S]
    return y.astype(x.dtype), h


def ssd_decode_ref(h, xt, dtt, A, bt, ct):
    """One decode step. h: (B,H,P,N); xt: (B,H,P); dtt: (B,H); bt, ct: (B,N).
    Returns (y (B,H,P), h')."""
    a = jnp.exp(dtt.astype(jnp.float32) * A)
    h = h.astype(jnp.float32) * a[..., None, None] + jnp.einsum(
        "bhp,bn,bh->bhpn", xt.astype(jnp.float32), bt.astype(jnp.float32),
        dtt.astype(jnp.float32))
    y = jnp.einsum("bhpn,bn->bhp", h, ct.astype(jnp.float32))
    return y.astype(xt.dtype), h


# ----------------------------------------------------------------- mLSTM ----

def mlstm_ref(q, k, v, log_i, log_f, *, state=None):
    """Sequential stabilized mLSTM oracle (xLSTM eq. 19-27).

    q, k: (B,S,H,Dk); v: (B,S,H,Dv); log_i, log_f: (B,S,H) pre-activation gate
    logs (log_f = logsigmoid(f_pre), log_i = i_pre). Returns (h (B,S,H,Dv),
    (C, n, m) final state) with C: (B,H,Dk,Dv), n: (B,H,Dk), m: (B,H).
    """
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    scale = Dk ** -0.5
    if state is None:
        C = jnp.zeros((B, H, Dk, Dv), jnp.float32)
        n = jnp.zeros((B, H, Dk), jnp.float32)
        m = jnp.full((B, H), -jnp.inf, jnp.float32)
    else:
        C, n, m = state

    def step(carry, inp):
        C, n, m = carry
        qt, kt, vt, li, lf = inp
        m_new = jnp.maximum(lf + m, li)
        i_p = jnp.exp(li - m_new)
        f_p = jnp.exp(lf + m - m_new)
        # first step: m == -inf makes f_p nan via inf-inf; define it as 0
        f_p = jnp.where(jnp.isfinite(m), f_p, 0.0)
        C = C * f_p[..., None, None] + i_p[..., None, None] * jnp.einsum(
            "bhk,bhv->bhkv", kt.astype(jnp.float32) * scale, vt.astype(jnp.float32))
        n = n * f_p[..., None] + i_p[..., None] * kt.astype(jnp.float32) * scale
        qf = qt.astype(jnp.float32)
        num = jnp.einsum("bhkv,bhk->bhv", C, qf)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, qf)),
                          jnp.exp(-m_new))
        h = num / den[..., None]
        return (C, n, m_new), h

    xs = tuple(a.swapaxes(0, 1) for a in (q, k, v, log_i, log_f))
    (C, n, m), hs = jax.lax.scan(step, (C, n, m), xs)
    return hs.swapaxes(0, 1).astype(v.dtype), (C, n, m)


def mlstm_chunked_ref(q, k, v, log_i, log_f, *, chunk: int = 64, state=None):
    """Chunkwise-parallel stabilized mLSTM == mlstm_ref to fp tolerance.

    Per chunk (length L, cumulative forget F_t = Σ_{s<=t} lf_s, u_s = li_s -
    F_s, running max g_t = max_{s<=t} u_s, M_t = max(m_prev, g_t)):

        h_t  = exp(m_prev - M_t) (q_t C_prev) +
               Σ_{s<=t} exp(u_s - M_t) (q_t.k_s) v_s           (all matmuls)
        n_t  analogous;  den_t = max(|n_t.q_t|, exp(-(F_t + M_t)))
        C' = exp(m_prev - M_L) C_prev + Σ_s exp(u_s - M_L) k_s v_s^T
        m' = F_L + M_L

    This removes the per-timestep scan: saved state is per *chunk*, and the
    intra-chunk work is (L x L) masked matmuls — the memory/compute shape the
    Pallas kernel (and the xlstm train-cell §Perf fix) wants.
    """
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    L = min(chunk, S)
    assert S % L == 0
    K = S // L
    scale = Dk ** -0.5
    if state is None:
        C = jnp.zeros((B, H, Dk, Dv), jnp.float32)
        n = jnp.zeros((B, H, Dk), jnp.float32)
        m = jnp.full((B, H), -jnp.inf, jnp.float32)
    else:
        C, n, m = state

    qc = q.reshape(B, K, L, H, Dk).astype(jnp.float32)
    kc = k.reshape(B, K, L, H, Dk).astype(jnp.float32) * scale
    vc = v.reshape(B, K, L, H, Dv).astype(jnp.float32)
    lic = log_i.reshape(B, K, L, H).astype(jnp.float32)
    lfc = log_f.reshape(B, K, L, H).astype(jnp.float32)

    F = jnp.cumsum(lfc, axis=2)                    # (B,K,L,H)
    u = lic - F
    g = jax.lax.cummax(u, axis=2)
    Ftot = F[:, :, -1]                             # (B,K,H)

    mask = jnp.tril(jnp.ones((L, L), bool))

    def body(carry, inp):
        C, n, m = carry                            # (B,H,Dk,Dv),(B,H,Dk),(B,H)
        qk_, kk, vk, Fk, uk, gk, Ft = inp
        M = jnp.maximum(m[:, None, :], gk)         # (B,L,H)
        w_state = jnp.exp(m[:, None, :] - M)       # (B,L,H)
        w_state = jnp.where(jnp.isfinite(m)[:, None, :], w_state, 0.0)
        # intra weights: W[t,s] = exp(u_s - M_t) for s <= t (mask pre-exp)
        diff = uk[:, None, :, :] - M[:, :, None, :]          # (B,t,s,H)
        W = jnp.exp(jnp.where(mask[None, :, :, None], diff, -jnp.inf))
        scores = jnp.einsum("bthd,bshd->btsh", qk_, kk)      # (B,t,s,H)
        num = jnp.einsum("btsh,bshv->bthv", scores * W, vk) + \
            jnp.einsum("bthd,bhdv->bthv", qk_, C) * w_state[..., None]
        # normalizer: n_t = w_state * n_prev + Σ_{s<=t} exp(u_s - M_t) k_s
        nvec = jnp.einsum("btsh,bshd->bthd", W, kk) + \
            n[:, None] * w_state[..., None]
        den = jnp.abs(jnp.einsum("bthd,bthd->bth", nvec, qk_))
        m_t = Fk + M                               # (B,L,H)
        den = jnp.maximum(den, jnp.exp(-m_t))
        h = num / den[..., None]

        # chunk-end state
        ML = jnp.maximum(m, gk[:, -1])             # (B,H)
        ws = jnp.exp(jnp.where(jnp.isfinite(m), m - ML, -jnp.inf))
        wk = jnp.exp(uk - ML[:, None, :])          # (B,L,H)
        C = C * ws[..., None, None] + jnp.einsum("bshd,bshv,bsh->bhdv",
                                                 kk, vk, wk)
        n = n * ws[..., None] + jnp.einsum("bshd,bsh->bhd", kk, wk)
        m = Ft + ML
        return (C, n, m), h

    xs = tuple(a.swapaxes(0, 1) for a in (qc, kc, vc, F, u, g, Ftot))
    (C, n, m), hs = jax.lax.scan(body, (C, n, m), xs)
    h = hs.swapaxes(0, 1).reshape(B, S, H, Dv)
    return h.astype(v.dtype), (C, n, m)


def slstm_ref(x_ifzo, *, state=None, r_ifzo=None):
    """Sequential sLSTM with exponential input gate + normalizer/stabilizer.

    x_ifzo: (B, S, H, 4, D) pre-activations for i, f, z, o per head;
    r_ifzo: optional recurrent weights (H, 4, D, D) applied to h_{t-1}.
    Returns (h (B,S,H,D), final state (c, n, m, h)).
    """
    B, S, H, four, D = x_ifzo.shape
    if state is None:
        c = jnp.zeros((B, H, D), jnp.float32)
        n = jnp.zeros((B, H, D), jnp.float32)
        m = jnp.full((B, H, D), -jnp.inf, jnp.float32)
        h = jnp.zeros((B, H, D), jnp.float32)
    else:
        c, n, m, h = state

    def step(carry, xt):
        c, n, m, h = carry
        pre = xt.astype(jnp.float32)
        if r_ifzo is not None:
            pre = pre + jnp.einsum("bhd,hgde->bhge", h, r_ifzo.astype(jnp.float32))
        i_p, f_p, z_p, o_p = pre[:, :, 0], pre[:, :, 1], pre[:, :, 2], pre[:, :, 3]
        lf = jax.nn.log_sigmoid(f_p)
        m_new = jnp.maximum(lf + m, i_p)
        i_g = jnp.exp(i_p - m_new)
        f_g = jnp.where(jnp.isfinite(m), jnp.exp(lf + m - m_new), 0.0)
        c = f_g * c + i_g * jnp.tanh(z_p)
        n = f_g * n + i_g
        h = jax.nn.sigmoid(o_p) * c / jnp.maximum(n, 1.0)
        return (c, n, m_new, h), h

    (c, n, m, h), hs = jax.lax.scan(step, (c, n, m, h), x_ifzo.swapaxes(0, 1))
    return hs.swapaxes(0, 1).astype(x_ifzo.dtype), (c, n, m, h)
