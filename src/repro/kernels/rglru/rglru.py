"""RG-LRU linear-recurrence Pallas kernel (Griffin / recurrentgemma).

Computes ``h_t = a_t * h_{t-1} + b_t`` over time, given per-step decays
``a`` and inputs ``b`` (the gate/decay math stays in XLA where it is
matmul-bound).  Grid: (batch, d_blocks, s_blocks); the sequence axis is
sequential ("arbitrary") with the carried state in VMEM scratch, so
arbitrarily long sequences stream through fixed VMEM.

Block: (1, bs, bd) with bd a multiple of 128 (vector-lane aligned).

Backward ("scan reversal"): the adjoint recurrence

    g_t = dh_t + a_{t+1} g_{t+1};    da_t = g_t * h_{t-1};    db_t = g_t

runs in :func:`rglru_scan_bwd` with the *sequence axis reversed* in the
grid index maps and the decayed adjoint carry ``c_t = a_t * g_t`` in
VMEM scratch — the mirror image of the forward kernel.  ``h_prev``
(h shifted right by one step, zero-initialised) is precomputed by the
caller from the forward output, so no state recomputation is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rglru_body(a_ref, b_ref, o_ref, h_ref, *, bs: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0]  # (bs, bd) fp32
    b = b_ref[0]

    def step(t, h):
        h = a[t] * h + b[t]
        o_ref[0, t, :] = h
        return h

    h_ref[0] = jax.lax.fori_loop(0, bs, step, h_ref[0])


def rglru_scan(
    a: jax.Array,  # (batch, seq, d) fp32 per-step decay
    b: jax.Array,  # (batch, seq, d) fp32 gated input
    *,
    bd: int = 256,
    bs: int = 256,
    interpret: bool = False,
) -> jax.Array:
    bsz, s, d = a.shape
    bd = min(bd, d)
    bs = min(bs, s)
    assert d % bd == 0 and s % bs == 0
    grid = (bsz, d // bd, s // bs)
    return pl.pallas_call(
        functools.partial(_rglru_body, bs=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, bd), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, bs, bd), lambda bi, di, si: (bi, si, di)),
        ],
        out_specs=pl.BlockSpec((1, bs, bd), lambda bi, di, si: (bi, si, di)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="rglru_scan",
        interpret=interpret,
    )(a, b)


def _rglru_bwd_body(a_ref, hp_ref, dh_ref, da_ref, db_ref, c_ref, *, bs: int):
    @pl.when(pl.program_id(2) == 0)  # reverse order: last block first
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    a = a_ref[0]  # (bs, bd)
    hp = hp_ref[0]  # h_{t-1}
    dh = dh_ref[0]

    def step(i, c):
        t = bs - 1 - i
        g = dh[t] + c
        da_ref[0, t, :] = g * hp[t]
        db_ref[0, t, :] = g
        return a[t] * g

    c_ref[0] = jax.lax.fori_loop(0, bs, step, c_ref[0])


def rglru_scan_bwd(
    a: jax.Array,  # (batch, seq, d) fp32 per-step decay
    h_prev: jax.Array,  # (batch, seq, d) fp32: h shifted right one step
    dh: jax.Array,  # (batch, seq, d) fp32 output cotangent
    *,
    bd: int = 256,
    bs: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Adjoint of :func:`rglru_scan`: returns (da, db)."""
    bsz, s, d = a.shape
    bd = min(bd, d)
    bs = min(bs, s)
    assert d % bd == 0 and s % bs == 0
    ns = s // bs
    rev = lambda si: ns - 1 - si  # noqa: E731 — reverse-scan index map
    spec = pl.BlockSpec((1, bs, bd), lambda bi, di, si: (bi, rev(si), di))
    return pl.pallas_call(
        functools.partial(_rglru_bwd_body, bs=bs),
        grid=(bsz, d // bd, ns),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),
            jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="rglru_scan_bwd",
        interpret=interpret,
    )(a, h_prev, dh)
