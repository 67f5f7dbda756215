"""Mamba-2 SSD chunked-scan Pallas kernel.

Computes the SSD core given pre-computed dt-scaled inputs and log-decays
(projections/conv/gating stay in XLA):

    H_t = exp(l_t) * H_{t-1} + xdt_t (x) B_t
    y_t = C_t . H_t

Grid: (batch, heads, chunks); the chunk axis is sequential ("arbitrary"),
carrying the (P x N) state in VMEM scratch — the TPU analogue of the
mamba2 Triton kernel's split into intra-chunk (quadratic, MXU-friendly)
and inter-chunk (recurrent) terms.  B/C are shared across heads (single
group) and indexed by (batch, chunk) only — no per-head duplication.

Backward ("scan reversal"): the forward can checkpoint the chunk-initial
states (``ssd_scan(..., return_states=True)``, one (P, N) tile per
chunk), and :func:`ssd_scan_bwd` walks the chunks **in reverse** —
the grid index maps flip ``ci -> nc-1-ci`` — carrying the adjoint state
G = dL/dH across chunks in VMEM scratch.  All per-chunk gradient terms
reduce to the same (Q, Q)/(Q, P)/(Q, N) matmuls the forward uses (plus
in-chunk cumsums for the log-decay gradient), so the MXU does the work
both ways.  dB/dC come out per head and are summed over heads by the
caller (B/C are head-shared).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_body(xdt_ref, b_ref, c_ref, lcum_ref, o_ref, *rest, q: int):
    s_ref = rest[0] if len(rest) == 2 else None
    h_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    if s_ref is not None:  # checkpoint the chunk-INITIAL state
        s_ref[0, 0, 0] = h_ref[...]

    xdt = xdt_ref[0, 0]  # (Q, P) fp32
    bmat = b_ref[0]  # (Q, N)
    cmat = c_ref[0]  # (Q, N)
    lcum = lcum_ref[0, 0]  # (Q, 1) within-chunk cumulative log decay

    # intra-chunk: y_i = sum_{j<=i} (C_i.B_j) exp(l_i - l_j) xdt_j
    scores = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32)
    seg = lcum - lcum.T  # (Q, Q) = l_i - l_j
    causal = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (q, q), 1
    )
    # mask inside the exp (j > i would overflow and NaN any grads)
    m = jnp.exp(jnp.where(causal, seg, -1e30)) * scores
    y = jnp.dot(m, xdt, preferred_element_type=jnp.float32)

    # inter-chunk: y_i += exp(l_i) * C_i . H_prev
    h_prev = h_ref[...]  # (P, N)
    y += jnp.exp(lcum) * jnp.dot(cmat, h_prev.T, preferred_element_type=jnp.float32)
    o_ref[0, 0] = y.astype(o_ref.dtype)

    # state update: H = exp(l_Q) H_prev + sum_j exp(l_Q - l_j) xdt_j (x) B_j
    ltot = lcum[q - 1, 0]
    w = jnp.exp(ltot - lcum)  # (Q, 1)
    h_ref[...] = jnp.exp(ltot) * h_prev + jnp.dot(
        (xdt * w).T, bmat, preferred_element_type=jnp.float32
    )


def ssd_scan(
    xdt: jax.Array,  # (batch, heads, seq, P) fp32: dt_t * x_t
    b: jax.Array,  # (batch, seq, N) fp32
    c: jax.Array,  # (batch, seq, N) fp32
    lcum_chunk: jax.Array,  # (batch, heads, seq, 1) fp32: within-chunk cumsum(log a)
    *,
    chunk: int = 128,
    return_states: bool = False,
    interpret: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Returns y — or ``(y, states)`` with ``return_states=True``, where
    ``states[b, h, ci]`` is the (P, N) state at the *start* of chunk ci
    (the checkpoint grid the backward kernel restarts from)."""
    bsz, h, s, p = xdt.shape
    n = b.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    grid = (bsz, h, nc)
    y_spec = pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0))
    out_specs = [y_spec]
    out_shape = [jax.ShapeDtypeStruct((bsz, h, s, p), jnp.float32)]
    if return_states:
        out_specs.append(
            pl.BlockSpec((1, 1, 1, p, n), lambda bi, hi, ci: (bi, hi, ci, 0, 0))
        )
        out_shape.append(jax.ShapeDtypeStruct((bsz, h, nc, p, n), jnp.float32))
    return pl.pallas_call(
        functools.partial(_ssd_body, q=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_specs=out_specs if return_states else y_spec,
        out_shape=out_shape if return_states else out_shape[0],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="ssd_scan",
        interpret=interpret,
    )(xdt, b, c, lcum_chunk)


def _suffix_sum(x, axis):
    """Inclusive suffix cumsum without flips (Mosaic-friendlier):
    suffix[i] = total - (prefix[i] - x[i])."""
    return x.sum(axis=axis, keepdims=True) - (jnp.cumsum(x, axis=axis) - x)


def _ssd_bwd_body(
    xdt_ref, b_ref, c_ref, lcum_ref, st_ref, dy_ref,
    dx_ref, db_ref, dc_ref, dl_ref, g_ref,
    *, q: int,
):
    """One reverse-order chunk of the SSD adjoint.

    Carries G = dL/d(chunk-final state) in ``g_ref``; every term below
    is the hand-derived adjoint of the forward body's three matmuls:

        y_i = sum_{j<=i} e^{l_i - l_j} (C_i.B_j) xdt_j + e^{l_i} C_i H_in
        H_out = e^{ltot} H_in + sum_j e^{ltot - l_j} xdt_j (x) B_j

    with l_i the inclusive within-chunk cumsum of log-decays.  The
    log-decay gradient needs "sums over the causal quadrant j < t <= i"
    of the elementwise product Z = decay * scores * (dy.xdt^T) — those
    are two in-chunk cumsums plus a diagonal pick, not extra matmuls.
    """
    @pl.when(pl.program_id(2) == 0)  # reverse order: last chunk first
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    xdt = xdt_ref[0, 0]  # (Q, P)
    bmat = b_ref[0]  # (Q, N)
    cmat = c_ref[0]  # (Q, N)
    lcum = lcum_ref[0, 0]  # (Q, 1)
    h_in = st_ref[0, 0, 0]  # (P, N) chunk-initial state (checkpoint)
    dy = dy_ref[0, 0]  # (Q, P)
    g = g_ref[...]  # (P, N) adjoint of the chunk-final state

    ltot = lcum[q - 1, 0]
    w = jnp.exp(lcum)  # (Q, 1): e^{l_i}
    v = jnp.exp(ltot - lcum)  # (Q, 1): e^{ltot - l_j}

    causal = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (q, q), 1
    )
    seg = lcum - lcum.T  # l_i - l_j
    decay = jnp.exp(jnp.where(causal, seg, -1e30))  # 0 above the diagonal
    scores = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32)
    m = decay * scores  # forward's intra-chunk kernel matrix
    t_mat = jnp.dot(dy, xdt.T, preferred_element_type=jnp.float32)
    dt_mat = decay * t_mat

    # dxdt_j = sum_{i>=j} M_ij dy_i  +  e^{ltot-l_j} (G B_j)
    dx_ref[0, 0] = (
        jnp.dot(m.T, dy, preferred_element_type=jnp.float32)
        + v * jnp.dot(bmat, g.T, preferred_element_type=jnp.float32)
    )
    # dC_i = sum_{j<=i} decay_ij T_ij B_j  +  e^{l_i} dy_i H_in
    dc_ref[0, 0] = (
        jnp.dot(dt_mat, bmat, preferred_element_type=jnp.float32)
        + w * jnp.dot(dy, h_in, preferred_element_type=jnp.float32)
    )
    # dB_j = sum_{i>=j} decay_ij T_ij C_i  +  e^{ltot-l_j} (xdt_j G)
    db_ref[0, 0] = (
        jnp.dot(dt_mat.T, cmat, preferred_element_type=jnp.float32)
        + v * jnp.dot(xdt, g, preferred_element_type=jnp.float32)
    )

    # d(log a_t), four terms (see module docstring derivation):
    #   (a) intra-chunk pairs j < t <= i of Z = decay*scores*T
    z = m * t_mat
    p1 = _suffix_sum(z, axis=0)  # P1[t, j] = sum_{i>=t} Z_ij
    excl = jnp.cumsum(p1, axis=1) - p1  # sum_{j<t} P1[t, j] at col t
    eye = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) == jax.lax.broadcasted_iota(
        jnp.int32, (q, q), 1
    )
    dl = jnp.sum(jnp.where(eye, excl, 0.0), axis=1, keepdims=True)
    #   (b) H_in reaching y_i (i >= t) through e^{l_i}
    u = w * jnp.sum(
        jnp.dot(dy, h_in, preferred_element_type=jnp.float32) * cmat,
        axis=1, keepdims=True,
    )
    dl += _suffix_sum(u, axis=0)
    #   (c) xdt_j (j < t) reaching the chunk-final state through e^{ltot-l_j}
    r = v * jnp.sum(
        jnp.dot(xdt, g, preferred_element_type=jnp.float32) * bmat,
        axis=1, keepdims=True,
    )
    dl += jnp.cumsum(r, axis=0) - r
    #   (d) H_in reaching the chunk-final state through e^{ltot} (every t)
    dl += jnp.exp(ltot) * jnp.sum(h_in * g)
    dl_ref[0, 0] = dl

    # carry: adjoint of THIS chunk's initial state = e^{ltot} G + sum_i e^{l_i} dy_i (x) C_i
    g_ref[...] = jnp.exp(ltot) * g + jnp.dot(
        (dy * w).T, cmat, preferred_element_type=jnp.float32
    )


def ssd_scan_bwd(
    xdt: jax.Array,  # (batch, heads, seq, P) fp32
    b: jax.Array,  # (batch, seq, N) fp32
    c: jax.Array,  # (batch, seq, N) fp32
    lcum_chunk: jax.Array,  # (batch, heads, seq, 1) fp32
    states: jax.Array,  # (batch, heads, nc, P, N) fp32 chunk-initial states
    dy: jax.Array,  # (batch, heads, seq, P) fp32 output cotangent
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Adjoint of :func:`ssd_scan`: (dxdt, db_per_head, dc_per_head,
    dlog_a).  db/dc are (batch, heads, seq, N) — sum over heads for the
    head-shared B/C inputs.  dlog_a is (batch, heads, seq, 1), already
    w.r.t. the *per-step* log-decays (not the within-chunk cumsum)."""
    bsz, h, s, p = xdt.shape
    n = b.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    rev = lambda ci: nc - 1 - ci  # noqa: E731 — reverse-chunk index map
    return pl.pallas_call(
        functools.partial(_ssd_bwd_body, q=chunk),
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, rev(ci), 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, rev(ci), 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, rev(ci), 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, rev(ci), 0)),
            pl.BlockSpec((1, 1, 1, p, n), lambda bi, hi, ci: (bi, hi, rev(ci), 0, 0)),
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, rev(ci), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, rev(ci), 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, hi, rev(ci), 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, hi, rev(ci), 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, rev(ci), 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, s, n), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, s, n), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, s, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="ssd_scan_bwd",
        interpret=interpret,
    )(xdt, b, c, lcum_chunk, states, dy)
