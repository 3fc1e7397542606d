"""The held experts of one expert-parallel shard as one Pallas TPU kernel.

An expert layer under expert parallelism holds experts
``[first, first + E)`` of the router's ``num_experts`` and computes only
their part of the result (models/moe.py::held_moe).  Routing is dropless
and the shapes are static: every held expert runs its gated MLP over every
row, and each row's result is weighted by its combine weight for that
expert — the routed gate weight where the router chose the expert, 0 where
it did not — so no row is ever dropped and nothing depends on how the
router spread the rows.  At decode (a few rows) the layer's time is the
held experts' weights streamed from HBM once, which any layer holding them
pays; at prefill the rows a held expert was not chosen for are computed
and multiplied by 0.

Layout: x (T, d); comb (E, T, 1) float32; wg / wi (E, d, F); wo (E, F, d).
The grid is (row block, expert, F block), the F blocks innermost: each
step streams one ``(d, bf)`` block of the gate and up projections and the
matching ``(bf, d)`` block of the down projection, and adds
``comb * (act(x wg) * (x wi)) wo`` into a float32 ``(bt, d)`` accumulator
that is written once per row block.  The hidden activation is rounded to
the weights' dtype before the down projection, as a bf16 program would.

Oracle: repro.kernels.ref.held_experts_ref (the same arithmetic, summed in
another order).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models.layers import act_fn

ROW_BLOCK = 256


def _kernel(x_ref, c_ref, wg_ref, wi_ref, wo_ref, o_ref, acc, *, act):
    e, f = pl.program_id(1), pl.program_id(2)

    @pl.when((e == 0) & (f == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wi_ref[...], preferred_element_type=jnp.float32)
    h = (act_fn(act)(g) * u).astype(x.dtype)
    y = jnp.dot(h, wo_ref[...], preferred_element_type=jnp.float32)
    acc[...] += c_ref[...] * y

    @pl.when((e == pl.num_programs(1) - 1) & (f == pl.num_programs(2) - 1))
    def _final():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def held_experts(x, comb, wg, wi, wo, *, act: str = "silu",
                 interpret: bool = False):
    """x: (T, d); comb: (T, E) combine weights of the held experts; wg / wi
    (E, d, F), wo (E, F, d).  Returns (T, d) in x's dtype:
    ``sum_e comb[:, e] * (act(x wg[e]) * (x wi[e])) wo[e]``."""
    T, d = x.shape
    E, _, F = wg.shape
    bf = 128 if F % 128 == 0 else F
    bt = min(ROW_BLOCK, -(-T // 16) * 16)
    Tp = -(-T // bt) * bt
    if Tp != T:
        x = jnp.pad(x, ((0, Tp - T), (0, 0)))
        comb = jnp.pad(comb, ((0, Tp - T), (0, 0)))
    c = jnp.transpose(comb.astype(jnp.float32))[:, :, None]     # (E, Tp, 1)
    out = pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid=(Tp // bt, E, F // bf),
        in_specs=[
            pl.BlockSpec((bt, d), lambda t, e, f: (t, 0)),
            pl.BlockSpec((None, bt, 1), lambda t, e, f: (e, t, 0)),
            pl.BlockSpec((None, d, bf), lambda t, e, f: (e, 0, f)),
            pl.BlockSpec((None, d, bf), lambda t, e, f: (e, 0, f)),
            pl.BlockSpec((None, bf, d), lambda t, e, f: (e, f, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda t, e, f: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="held_experts",
    )(x, c, wg, wi, wo)
    return out[:T]
