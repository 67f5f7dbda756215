"""Blockwise (flash) attention Pallas kernel with GQA + local windows.

Grid: (batch * n_heads, q_blocks, kv_blocks); the kv axis is sequential
("arbitrary") so the running-softmax state (m, l, acc) lives in VMEM
scratch across kv steps.  GQA is handled in the K/V index maps (query
head h reads kv head h // group) — no materialised head repetition.
Causal and sliding-window masks are position-based, computed in-kernel.

VMEM working set per step: bq*d + bk*d (+ bq*bk fp32 scores), MXU-aligned
defaults bq = bk = 128, head_dim padded to a multiple of 128 upstream.

Backward pass (FlashAttention-2 style, recompute-based): the forward can
additionally emit the per-row log-sum-exp (``return_lse=True``) and the
backward never materialises the (sq, sk) probability matrix — it
recomputes scores blockwise from q/k and normalises with the saved lse.
Two kernels, mirroring the usual TPU split:

* :func:`flash_attention_bwd_dq` — grid (b*h, q_blocks, kv_blocks), kv
  sequential, dQ accumulated in VMEM scratch across kv steps;
* :func:`flash_attention_bwd_dkv` — grid (b*h, kv_blocks, q_blocks), q
  sequential, dK/dV accumulated in scratch; gradients come out per
  *query* head and are group-summed to the kv heads by the caller (GQA).

Both take ``delta = rowsum(dO * O)`` precomputed outside (one cheap
elementwise pass) — the standard trick that removes the second
normaliser reduction from the inner loop.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0**30


def _mask(bq: int, bk: int, qi, ki, causal: bool, window: int | None):
    """Position-based causal / sliding-window mask for one (bq, bk) tile."""
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    return mask


def _flash_body(
    q_ref, k_ref, v_ref, o_ref, *rest,
    kv_steps: int, bq: int, bk: int, causal: bool, window: int | None,
    scale: float, softcap: float | None,
):
    lse_ref = rest[0] if len(rest) == 4 else None
    m_ref, l_ref, acc_ref = rest[-3:]
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]  # (bq, d)
    k = k_ref[0, 0]  # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    s = jnp.where(_mask(bq, bk, pl.program_id(1), ki, causal, window), s, NEG_INF)

    m_prev = m_ref[...]  # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0, 0], preferred_element_type=jnp.float32
    )

    @pl.when(ki == kv_steps - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = (m_ref[..., 0] + jnp.log(l[..., 0])).astype(lse_ref.dtype)


def flash_attention(
    q: jax.Array,  # (batch, n_heads, seq_q, head_dim)
    k: jax.Array,  # (batch, n_kv_heads, seq_k, head_dim)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    bq: int = 128,
    bk: int = 128,
    return_lse: bool = False,
    interpret: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Returns O — or ``(O, lse)`` with ``return_lse=True``, where
    ``lse[b, h, i] = log sum_j exp(s_ij)`` (fp32) is the softmax
    normaliser the backward kernels rescale recomputed scores with."""
    b, h, sq, d = q.shape
    _, kvh, sk, _ = k.shape
    assert h % kvh == 0
    group = h // kvh
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    kv_steps = sk // bk
    grid = (b * h, sq // bq, kv_steps)
    scale = 1.0 / math.sqrt(d)

    body = functools.partial(
        _flash_body, kv_steps=kv_steps, bq=bq, bk=bk, causal=causal,
        window=window, scale=scale, softcap=softcap,
    )
    o_spec = pl.BlockSpec((1, 1, bq, d), lambda bh, qi, ki: (bh // h, bh % h, qi, 0))
    out_specs = [o_spec]
    out_shape = [jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)]
    if return_lse:
        out_specs.append(
            pl.BlockSpec((1, 1, bq), lambda bh, qi, ki: (bh // h, bh % h, qi))
        )
        out_shape.append(jax.ShapeDtypeStruct((b, h, sq), jnp.float32))
    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bh, qi, ki: (bh // h, bh % h, qi, 0)),
            pl.BlockSpec(
                (1, 1, bk, d), lambda bh, qi, ki: (bh // h, (bh % h) // group, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, d), lambda bh, qi, ki: (bh // h, (bh % h) // group, ki, 0)
            ),
        ],
        out_specs=out_specs if return_lse else o_spec,
        out_shape=out_shape if return_lse else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # running max
            pltpu.VMEM((bq, 1), jnp.float32),  # running denominator
            pltpu.VMEM((bq, d), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
    return out


def _bwd_scores(q, k, do, v, lse, delta, qi, ki, *, bq, bk, scale, causal,
                window, softcap):
    """Shared backward-tile math: recompute p from (q, k, lse), return
    (p, ds) where ds is the gradient w.r.t. the *raw* (pre-scale) scores.

    q/do: (bq, d); k/v: (bk, d); lse/delta: (bq, 1).  All fp32.
    """
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        th = jnp.tanh(s / softcap)
        s = softcap * th
    masked = _mask(bq, bk, qi, ki, causal, window)
    s = jnp.where(masked, s, NEG_INF)
    p = jnp.exp(s - lse)  # masked -> exp(NEG_INF - lse) == 0
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta)  # d(softcapped, scaled) scores
    if softcap is not None:
        ds = ds * (1.0 - th * th)
    return p, ds * scale


def _flash_bwd_dq_body(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, kv_steps: int, bq: int, bk: int, causal: bool, window: int | None,
    scale: float, softcap: float | None,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _, ds = _bwd_scores(
        q_ref[0, 0].astype(jnp.float32), k_ref[0, 0].astype(jnp.float32),
        do_ref[0, 0].astype(jnp.float32), v_ref[0, 0].astype(jnp.float32),
        lse_ref[0, 0][:, None], delta_ref[0, 0][:, None],
        pl.program_id(1), ki, bq=bq, bk=bk, scale=scale, causal=causal,
        window=window, softcap=softcap,
    )
    acc_ref[...] += jnp.dot(
        ds, k_ref[0, 0].astype(jnp.float32), preferred_element_type=jnp.float32
    )

    @pl.when(ki == kv_steps - 1)
    def _flush():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_body(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, q_steps: int, bq: int, bk: int, causal: bool, window: int | None,
    scale: float, softcap: float | None,
):
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    p, ds = _bwd_scores(
        q, k_ref[0, 0].astype(jnp.float32), do,
        v_ref[0, 0].astype(jnp.float32),
        lse_ref[0, 0][:, None], delta_ref[0, 0][:, None],
        qi, pl.program_id(1), bq=bq, bk=bk, scale=scale, causal=causal,
        window=window, softcap=softcap,
    )
    dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
    dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(qi == q_steps - 1)
    def _flush():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_common(q, k):
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    assert h % kvh == 0
    return b, h, sq, d, kvh, sk, h // kvh, 1.0 / math.sqrt(d)


def flash_attention_bwd_dq(
    q, k, v, do, lse, delta,
    *, causal=True, window=None, softcap=None, bq=128, bk=128, interpret=False,
) -> jax.Array:
    """dQ for :func:`flash_attention`.  ``lse``/``delta``: (b, h, sq) fp32
    (delta = rowsum(dO * O)).  Returns dQ with q's shape and dtype."""
    b, h, sq, d, kvh, sk, group, scale = _bwd_common(q, k)
    bq, bk = min(bq, sq), min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    kv_steps = sk // bk
    body = functools.partial(
        _flash_bwd_dq_body, kv_steps=kv_steps, bq=bq, bk=bk, causal=causal,
        window=window, scale=scale, softcap=softcap,
    )
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bh, qi, ki: (bh // h, bh % h, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d), lambda bh, qi, ki: (bh // h, (bh % h) // group, ki, 0)
    )
    row_spec = pl.BlockSpec((1, 1, bq), lambda bh, qi, ki: (bh // h, bh % h, qi))
    return pl.pallas_call(
        body,
        grid=(b * h, sq // bq, kv_steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def flash_attention_bwd_dkv(
    q, k, v, do, lse, delta,
    *, causal=True, window=None, softcap=None, bq=128, bk=128, interpret=False,
) -> tuple[jax.Array, jax.Array]:
    """dK/dV for :func:`flash_attention`, **per query head**: both come
    out (b, h, sk, d); under GQA the caller sums each group of
    ``h // kvh`` query heads down to its kv head (exact — addition)."""
    b, h, sq, d, kvh, sk, group, scale = _bwd_common(q, k)
    bq, bk = min(bq, sq), min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    q_steps = sq // bq
    body = functools.partial(
        _flash_bwd_dkv_body, q_steps=q_steps, bq=bq, bk=bk, causal=causal,
        window=window, scale=scale, softcap=softcap,
    )
    # note the grid transpose vs. dq: kv blocks parallel, q sequential
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bh, ki, qi: (bh // h, bh % h, qi, 0))
    kv_in_spec = pl.BlockSpec(
        (1, 1, bk, d), lambda bh, ki, qi: (bh // h, (bh % h) // group, ki, 0)
    )
    kv_out_spec = pl.BlockSpec(
        (1, 1, bk, d), lambda bh, ki, qi: (bh // h, bh % h, ki, 0)
    )
    row_spec = pl.BlockSpec((1, 1, bq), lambda bh, ki, qi: (bh // h, bh % h, qi))
    return pl.pallas_call(
        body,
        grid=(b * h, sk // bk, q_steps),
        in_specs=[q_spec, kv_in_spec, kv_in_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_out_spec, kv_out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
