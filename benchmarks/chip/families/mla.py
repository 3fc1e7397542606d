"""The DeepSeek-V2 decoder: multi-head latent attention and held experts.

Embedding, then per layer RMSNorm, MLA, a residual, RMSNorm, a feed-forward
and a residual; a final RMSNorm and an untied head.  MLA as published
(DeepSeek-V2, arXiv:2405.04434, and its ``config.json``) with
``q_lora_rank`` null: the query is one projection to ``H x (nope + rope)``;
the key and value come from a ``kv_lora_rank`` latent, RMS-normed
(``kv_a_layernorm``), expanded per head by ``w_uk`` / ``w_uv``, plus one
rope key shared by every head.  The rope dims are YaRN-scaled (rotate-half
on the rope dims' two halves, the frequencies blended across the
correction range of ``beta_fast`` / ``beta_slow``), and the softmax scale
is ``(nope + rope)^-0.5 x (0.1 x mscale_all_dim x ln(factor) + 1)^2``.
The first ``first_k_dense_replace`` layers have a dense SwiGLU MLP of
``intermediate_size``; every later one an expert layer: a router over all
``n_routed_experts`` (softmax in float32, the top ``num_experts_per_tok``
taken greedily, renormalised only if ``norm_topk_prob``; the published
``routed_scaling_factor`` is 1), SwiGLU experts of ``moe_intermediate_size``,
and shared experts that make one SwiGLU of ``n_shared_experts`` times that
width.

This chip holds the experts ``[0, held)`` of every expert layer, one chip
of an expert-parallel group (``reduced: ["n_routed_experts"]``): the
router keeps its published width, each token's result takes only the held
experts' part, and the shared experts count whole.  The reference is given
the same share.  The program runs layer 0 as its prologue and the expert
layers as one stacked period of ``mla`` layers.

Weights: one array per kind of matrix, the expert layers stacked on the
leading axis, drawn normal with std ``1/sqrt(fan_in)`` (embedding and head
``d_model^-0.5``); RMSNorm weights ``g = 1 + w``, ``w ~ N(0, NORM_STD)``,
which is how the program stores them.

The interface is the one ``families/__init__.py`` states; this family runs
the ``latent_decode`` kernel, not ``paged_decode``, and provides
``latent_decode_step`` for its reader.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import HIGHEST, fp8 as _fp8, mm, padded, rms
from benchmarks.chip.weights import NORM_STD, draw, key_of

# published key -> the program's ``ModelConfig`` field, checked equal
HF_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "num_layers",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta", "intermediate_size": "prologue_d_ff",
           "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_dim",
           "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim",
           "tie_word_embeddings": "tie_embeddings", "hidden_act": "act"}
# published key -> the program's ``MoEConfig`` field
MOE_KEYS = {"moe_intermediate_size": "d_ff",
            "num_experts_per_tok": "experts_per_token",
            "n_shared_experts": "num_shared_experts",
            "norm_topk_prob": "norm_topk_prob"}
# published rope_scaling key -> the program's ``YarnConfig`` field
YARN_KEYS = {"factor": "factor",
             "original_max_position_embeddings": "original_max_position",
             "beta_fast": "beta_fast", "beta_slow": "beta_slow",
             "mscale": "mscale", "mscale_all_dim": "mscale_all_dim"}
# published settings the program has no switch for: it runs exactly these
FIXED = {"q_lora_rank": None, "attention_bias": False, "moe_layer_freq": 1,
         "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
         "topk_group": 1, "routed_scaling_factor": 1}
CUTS = ("n_routed_experts",)


def _exit(name, msg):
    raise SystemExit(f"{name}: {msg}")


def program_config(file: dict, rehearse: bool):
    """The registry's configuration of ``file["arch"]``, checked against
    every published key above.  The one cut this family applies is
    ``n_routed_experts`` under ``reduced``: the file's count is the experts
    held here (``[0, held)``), its ``published`` count the router's width;
    any other key under ``reduced`` exits.  A program without the latent
    norm, YaRN or the held-expert layer exits too."""
    from repro.configs.base import get_config
    name = file["arch"]
    other = sorted(set(file.get("reduced", [])) - set(CUTS))
    if other:
        _exit(name, f"family mla cannot apply the cuts {other!r}; it "
                    f"applies {list(CUTS)!r}")
    cfg = get_config(name)
    moe = cfg.moe
    for field in ("kv_norm", "rope_yarn"):
        if not hasattr(cfg, field):
            _exit(name, f"the program has no {field}")
    if moe is None or not all(hasattr(moe, f) for f in
                              ("dropless", "num_held", "first_expert",
                               "norm_topk_prob")):
        _exit(name, "the program has no held-expert layer")
    for key, attr in HF_KEYS.items():
        if file[key] != getattr(cfg, attr):
            _exit(name, f"{key}={file[key]!r} but the program runs "
                        f"{attr}={getattr(cfg, attr)!r}")
    for key, attr in MOE_KEYS.items():
        if file[key] != getattr(moe, attr):
            _exit(name, f"{key}={file[key]!r} but the program runs "
                        f"moe.{attr}={getattr(moe, attr)!r}")
    for key, want in FIXED.items():
        if file[key] != want:
            _exit(name, f"{key}={file[key]!r}; the program runs {want!r}")
    ys = file["rope_scaling"]
    if ys.get("type") != "yarn" or cfg.rope_yarn is None:
        _exit(name, f"rope_scaling {ys!r} against {cfg.rope_yarn!r}")
    for key, attr in YARN_KEYS.items():
        if ys[key] != getattr(cfg.rope_yarn, attr):
            _exit(name, f"rope_scaling.{key}={ys[key]!r} but the program "
                        f"runs {getattr(cfg.rope_yarn, attr)!r}")
    if not (cfg.kv_norm and moe.dropless):
        _exit(name, "the program runs no latent norm or routes with "
                    "capacity")
    if (list(cfg.prologue) != ["mla"] * file["first_k_dense_replace"]
            or list(cfg.period) != ["mla"]):
        _exit(name, f"layer pattern {cfg.prologue} + {cfg.period}")
    if file["published"]["n_routed_experts"] != moe.num_experts:
        _exit(name, f"n_routed_experts published "
                    f"{file['published']['n_routed_experts']} but the "
                    f"router is {moe.num_experts} wide")
    held = int(file["n_routed_experts"])
    if "n_routed_experts" not in file.get("reduced", []) and \
            held != moe.num_experts:
        _exit(name, f"n_routed_experts {held} is a cut not listed in "
                    f"reduced")
    if cfg.dtype != file["serving"]["dtype"]:
        _exit(name, f"dtype {cfg.dtype}")
    cfg = dataclasses.replace(cfg, norm_eps=float(file["rms_norm_eps"]))
    if rehearse:
        small = cfg.reduced()
        held = max(1, small.moe.num_experts * held // moe.num_experts)
        cfg = dataclasses.replace(small, dtype=cfg.dtype)
        moe = cfg.moe
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        moe, first_expert=0, num_held=held))


@dataclass(frozen=True)
class Shape:
    """What the weights, the reference and the counts need of the model."""
    layers: int
    dense_layers: int       # leading dense layers (the program's prologue)
    d_model: int
    heads: int
    nope: int               # qk_nope_head_dim
    rope: int               # qk_rope_head_dim
    v_dim: int
    latent: int             # kv_lora_rank
    d_ff: int               # the dense layers' width
    expert_ff: int          # each expert's width
    experts: int            # the router's width (published)
    held: int               # experts [0, held) live on this chip
    topk: int
    shared_ff: int          # the shared experts as one SwiGLU's width
    norm_topk: bool
    vocab: int
    padded_vocab: int
    rope_theta: float
    yarn: tuple             # (factor, original positions, beta_fast,
                            #  beta_slow, mscale, mscale_all_dim)
    norm_eps: float
    tied: bool = False
    dtype: str = "bfloat16"

    @classmethod
    def of(cls, cfg) -> "Shape":
        m, y = cfg.moe, cfg.rope_yarn
        return cls(layers=cfg.num_layers, dense_layers=len(cfg.prologue),
                   d_model=cfg.d_model, heads=cfg.num_heads,
                   nope=cfg.qk_nope_dim, rope=cfg.qk_rope_dim,
                   v_dim=cfg.v_head_dim, latent=cfg.kv_lora_rank,
                   d_ff=cfg.prologue_d_ff, expert_ff=m.d_ff,
                   experts=m.num_experts, held=m.held,
                   topk=m.experts_per_token,
                   shared_ff=m.d_ff * m.num_shared_experts,
                   norm_topk=m.norm_topk_prob,
                   vocab=cfg.vocab_size, padded_vocab=cfg.padded_vocab,
                   rope_theta=float(cfg.rope_theta),
                   yarn=(float(y.factor), int(y.original_max_position),
                         float(y.beta_fast), float(y.beta_slow),
                         float(y.mscale), float(y.mscale_all_dim)),
                   norm_eps=cfg.norm_eps, tied=cfg.tie_embeddings,
                   dtype=cfg.dtype)

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers


# --------------------------------------------------------------- weights --

ATTN = ("ln1", "ln2", "wq", "wdkv", "wkr", "kvn", "wuk", "wuv", "wo")


def _attn_shapes(s: Shape, lead: tuple) -> dict:
    d, H = s.d_model, s.heads
    qd, vd = H * (s.nope + s.rope), H * s.v_dim
    return {"ln1": (lead + (d,), NORM_STD), "ln2": (lead + (d,), NORM_STD),
            "wq": (lead + (d, qd), d ** -0.5),
            "wdkv": (lead + (d, s.latent), d ** -0.5),
            "wkr": (lead + (d, s.rope), d ** -0.5),
            "kvn": (lead + (s.latent,), NORM_STD),
            "wuk": (lead + (s.latent, H * s.nope), s.latent ** -0.5),
            "wuv": (lead + (s.latent, vd), s.latent ** -0.5),
            "wo": (lead + (vd, d), vd ** -0.5)}


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, s: Shape):
    dt = jnp.bfloat16 if s.dtype == "bfloat16" else jnp.float32
    d, F, L, E = s.d_model, s.expert_ff, (s.moe_layers,), s.held
    shapes = {"embed": ((s.padded_vocab, d), d ** -0.5),
              "head": ((s.padded_vocab, d), d ** -0.5),
              "final_norm": ((d,), NORM_STD)}
    dense = (s.dense_layers,)
    shapes.update({f"d_{k}": v for k, v in _attn_shapes(s, dense).items()})
    shapes.update({"d_wg": (dense + (d, s.d_ff), d ** -0.5),
                   "d_wi": (dense + (d, s.d_ff), d ** -0.5),
                   "d_wd": (dense + (s.d_ff, d), s.d_ff ** -0.5)})
    shapes.update(_attn_shapes(s, L))
    shapes.update({"router": (L + (d, s.experts), d ** -0.5),
                   "eg": (L + (E, d, F), d ** -0.5),
                   "ei": (L + (E, d, F), d ** -0.5),
                   "ed": (L + (E, F, d), F ** -0.5),
                   "sg": (L + (d, s.shared_ff), d ** -0.5),
                   "si": (L + (d, s.shared_ff), d ** -0.5),
                   "sd": (L + (s.shared_ff, d), s.shared_ff ** -0.5)})
    return draw(key, shapes, dt)


def make(shape: Shape, seed: int) -> dict:
    return _make(key_of(seed), shape)


def _program_attn(w: dict, p: str, i=None) -> dict:
    def a(k):
        return w[p + k] if i is None else w[p + k][i]
    return {"wq": a("wq"), "w_dkv": a("wdkv"), "w_krope": a("wkr"),
            "kv_norm": a("kvn"), "w_uk": a("wuk"), "w_uv": a("wuv"),
            "wo": a("wo")}


def to_program(w: dict, shape: Shape) -> dict:
    """The program's parameter tree (``repro.models.model``) over the same
    arrays: the dense layers as the prologue, the expert layers as one
    stacked period whose expert banks hold the experts held here."""
    prologue = [{"ln1": w["d_ln1"][i], "ln2": w["d_ln2"][i],
                 "mla": _program_attn(w, "d_", i),
                 "mlp": {"wg": w["d_wg"][i], "wi": w["d_wi"][i],
                         "wo": w["d_wd"][i]}}
                for i in range(shape.dense_layers)]
    slot = {"ln1": w["ln1"], "ln2": w["ln2"], "mla": _program_attn(w, ""),
            "moe": {"router": w["router"], "wg": w["eg"], "wi": w["ei"],
                    "wo": w["ed"], "shared": {"wg": w["sg"], "wi": w["si"],
                                              "wo": w["sd"]}}}
    return {"embed": {"table": w["embed"]}, "final_norm": w["final_norm"],
            "head": w["head"],
            "stack": {"prologue": prologue, "slots": [slot]}}


def _attn_params(s: Shape) -> int:
    H = s.heads
    return s.d_model * (H * (s.nope + s.rope) + s.latent + s.rope) \
        + s.latent * H * (s.nope + s.v_dim) + H * s.v_dim * s.d_model


def param_count(shape: Shape) -> dict:
    """Matrix parameters held here: of the layer stack, and of the head."""
    s = shape
    dense = _attn_params(s) + 3 * s.d_model * s.d_ff
    moe = _attn_params(s) + s.d_model * s.experts \
        + 3 * s.d_model * (s.held * s.expert_ff + s.shared_ff)
    return {"stack": s.dense_layers * dense + s.moe_layers * moe,
            "head": s.vocab * s.d_model}


# ------------------------------------------------------------- reference --

def yarn_inv_freq(s: Shape):
    """YaRN's rotary inverse frequencies as DeepSeek-V2 publishes them."""
    factor, orig, beta_fast, beta_slow, _, _ = s.yarn
    dim, base = s.rope, s.rope_theta
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    extra, inter = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / \
            (2 * math.log(base))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(s: Shape) -> float:
    factor, _, _, _, _, mscale_all_dim = s.yarn
    return (s.nope + s.rope) ** -0.5 * _mscale(factor, mscale_all_dim) ** 2


def _rope(s: Shape, x, pos):
    """x: (T, n, rope); rotate-half on the YaRN frequencies, cos and sin
    scaled by ``mscale(mscale) / mscale(mscale_all_dim)``."""
    factor, _, _, _, msc, msc_all = s.yarn
    ang = pos[:, None].astype(jnp.float32) * yarn_inv_freq(s)[None, :]
    m = _mscale(factor, msc) / _mscale(factor, msc_all)
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    x1, x2 = x[..., :s.rope // 2], x[..., s.rope // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, wg, wi, wd, fp8):
    return mm(jax.nn.silu(mm(h, wg, fp8)) * mm(h, wi, fp8), wd, fp8)


def _attention(s: Shape, fp8: bool, x, lw):
    T = x.shape[0]
    H, dn = s.heads, s.nope
    pos = jnp.arange(T)
    h = rms(x, lw["ln1"], s.norm_eps)
    q = mm(h, lw["wq"], fp8).reshape(T, H, dn + s.rope)
    q_nope, q_pe = q[..., :dn], _rope(s, q[..., dn:], pos)
    ckv = rms(mm(h, lw["wdkv"], fp8), lw["kvn"], s.norm_eps)
    k_pe = _rope(s, mm(h, lw["wkr"], fp8)[:, None, :], pos)
    k_nope = mm(ckv, lw["wuk"], fp8).reshape(T, H, dn)
    v = mm(ckv, lw["wuv"], fp8).reshape(T, H, s.v_dim)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (T, H, s.rope))], -1)
    if fp8:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    scores = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) \
        * softmax_scale(s)
    causal = pos[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    if fp8:
        p = _fp8(p)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)
    return x + mm(o.reshape(T, H * s.v_dim), lw["wo"], fp8)


def _experts(s: Shape, fp8: bool, h, lw):
    """The held experts' part of the expert layer for every token, plus the
    shared experts.  Routing over all ``experts``: softmax in float32, the
    top ``topk`` probabilities, renormalised only if ``norm_topk``."""
    probs = jax.nn.softmax(mm(h, lw["router"], fp8), axis=-1)
    vals, idx = jax.lax.top_k(probs, s.topk)
    if s.norm_topk:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    held = jnp.arange(s.held)
    gate = jnp.sum(jnp.where(idx[:, :, None] == held, vals[:, :, None],
                             0.0), axis=1)                     # (T, held)
    y = jax.vmap(lambda g, i, d: _swiglu(h, g, i, d, fp8))(
        lw["eg"], lw["ei"], lw["ed"])                          # (held, T, d)
    out = jnp.einsum("te,etd->td", gate, y, precision=HIGHEST)
    return out + _swiglu(h, lw["sg"], lw["si"], lw["sd"], fp8)


def _dense_layer(s: Shape, fp8: bool, x, lw):
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    x = _attention(s, fp8, x, lw)
    h = rms(x, lw["ln2"], s.norm_eps)
    return x + _swiglu(h, lw["wg"], lw["wi"], lw["wd"], fp8), None


def _moe_layer(s: Shape, fp8: bool, x, lw):
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    x = _attention(s, fp8, x, lw)
    return x + _experts(s, fp8, rms(x, lw["ln2"], s.norm_eps), lw), None


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits_at(s: Shape, w, tokens, pos, fp8):
    x = w["embed"][tokens].astype(jnp.float32)
    dense = {k: w[f"d_{k}"] for k in ATTN + ("wg", "wi", "wd")}
    x, _ = jax.lax.scan(functools.partial(_dense_layer, s, fp8), x, dense)
    stack = {k: w[k] for k in ATTN + ("router", "eg", "ei", "ed", "sg",
                                      "si", "sd")}
    x, _ = jax.lax.scan(functools.partial(_moe_layer, s, fp8), x, stack)
    h = rms(x[pos], w["final_norm"].astype(jnp.float32), s.norm_eps)
    return mm(h, w["head"][:s.vocab].astype(jnp.float32).T, fp8)


def logits_at(s: Shape, w: dict, tokens, pos, length: int, n_pos: int,
              fp8: bool = False):
    """Logits (len(pos), vocab) of the sequence ``tokens`` at positions
    ``pos``, padded to ``length`` tokens and ``n_pos`` positions; the
    bf16 weights are widened to float32 one layer at a time."""
    return padded(functools.partial(_logits_at, s, w), tokens, pos, length,
                  n_pos, fp8)


# ------------------------------------------- counts (as flops.py counts) --

def latent_decode_call(s: Shape, lengths, dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of one ``latent_decode`` call, one layer: each
    active slot's ``heads`` absorbed queries score its ``lengths[b]`` valid
    latent rows (``latent + rope`` wide) and sum them as values
    (``latent`` wide).  Bytes are those rows, read once for every head,
    plus the queries read and the latent output written."""
    ctx = sum(int(n) for n in lengths)
    row = s.latent + s.rope
    flops = 2 * s.heads * ctx * (row + s.latent)
    q_out = len(lengths) * s.heads * (row + s.latent) * dtype_bytes
    return flops, ctx * row * dtype_bytes + q_out


def latent_decode_step(s: Shape, lengths) -> tuple:
    """(operations, bytes) of every ``latent_decode`` call of one decode
    step: one call in each layer."""
    flops, nbytes = latent_decode_call(s, lengths)
    return s.layers * flops, s.layers * nbytes


def routed_rows(s: Shape, tokens: int) -> float:
    """The rows the held experts compute usefully for ``tokens`` tokens:
    the expected routed rows ``tokens x topk x held / experts`` (8 slots:
    8 x 6 x 8 / 64 = 6 rows a layer), routing spread evenly."""
    return tokens * s.topk * s.held / s.experts


def decode_step(s: Shape, lengths) -> float:
    """Operations of one decode forward of the active slots: every matrix
    of attention (``w_uk`` / ``w_uv`` absorbed into the query and output,
    the same count), the dense MLPs, the router and shared experts of
    every expert layer and the head once per slot; the held experts at the
    expected routed rows (``routed_rows``); and the latent attention over
    each slot's context in every layer."""
    B = len(lengths)
    dense = _attn_params(s) + 3 * s.d_model * s.d_ff
    moe = _attn_params(s) + s.d_model * s.experts \
        + 3 * s.d_model * s.shared_ff
    per_token = s.dense_layers * dense + s.moe_layers * moe \
        + s.vocab * s.d_model
    experts = s.moe_layers * 2 * 3 * s.d_model * s.expert_ff \
        * routed_rows(s, B)
    attn, _ = latent_decode_step(s, lengths)
    return 2 * per_token * B + experts + attn
