"""Paged-attention pallas kernels: gather K/V *pages* via a block table.

The serving-side mirror of the matmul multicast schedules: the KV pages
of a shared prompt prefix exist once in HBM and every request's block
table points at them — the crossbar's "fetch once, deliver to N
consumers" applied to the KV cache.  Two kernels share that gather:

* :func:`paged_attention_decode` — one decode token per sequence
  (s == 1), bf16/fp32 pages;
* :func:`paged_attention_prefill` — the **chunked-prefill supertile**
  kernel: s >= 1 query tokens per sequence (prefix-hit suffix
  prefills), grid ``(batch, kv_heads, q_chunks, pages)``, where one
  K/V page fetch is multicast to all ``qc`` query rows of a chunk (the
  paper's supertile B-reuse applied to attention: K/V HBM traffic
  scales with ``ceil(s / qc)`` instead of ``s``), with ragged suffixes
  at true positions, causal masking vs. the per-sequence query start,
  GQA/MQA, softcap, and int8 pages **dequantised on gather** in-kernel
  (per-(page, slot) scales ride the same block-table index maps).

Decode layout / grid:

* ``q``            (batch, n_heads, head_dim) — one decode token per seq,
* ``k_pages``/``v_pages`` (kv_heads, num_pages, page_size, head_dim),
* ``block_table``  (batch, pages_per_seq) int32 page ids,
* ``lengths``      (batch,) int32 — tokens valid in each sequence
  (the decode token is position ``lengths - 1``).

Grid ``(batch, kv_heads, pages_per_seq)`` with the page axis sequential
("arbitrary"): the running-softmax state (m, l, acc) for the ``group =
n_heads / kv_heads`` query heads of one kv head lives in VMEM scratch
across page steps, exactly like the flash kernel's kv axis.  The
**block table rides the scalar-prefetch channel**
(``PrefetchScalarGridSpec``): K/V index maps read ``table[b, p]`` to
pick the page each grid step DMAs, so the gather happens in the
pipeline's address generation — no materialised contiguous KV copy.
Pages past a sequence's length still occupy grid steps (the table pads
with the null page 0) but skip all compute via ``pl.when``; the ragged
tail inside the last page is masked positionally.

Unused / padded table entries must be 0 (the pool's null page) so the
prefetched index is always in range.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0**30


def _paged_body(
    table_ref, start_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *, pages: int, ps: int, scale: float, softcap: float | None,
):
    bi = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[bi]
    qpos = start_ref[bi]  # the decode token's absolute position

    # pages at or past the length hold no valid tokens (their table
    # entries are the null page): skip the MXU work entirely
    @pl.when(pi * ps < length)
    def _compute():
        q = q_ref[0, 0]  # (group, d)
        k = k_ref[0, 0]  # (bk=ps, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # ragged tail + causality: key position pi*ps + j must be
        # within the sequence and not past the query token
        kpos = pi * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((kpos < length) & (kpos <= qpos), s, NEG_INF)

        m_prev = m_ref[...]  # (group, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0, 0], preferred_element_type=jnp.float32
        )

    @pl.when(pi == pages - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_decode(
    q: jax.Array,  # (batch, n_heads, head_dim)
    k_pages: jax.Array,  # (kv_heads, num_pages, page_size, head_dim)
    v_pages: jax.Array,
    block_table: jax.Array,  # (batch, pages_per_seq) int32
    start: jax.Array,  # (batch,) int32 — the decode token's position
    lengths: jax.Array,  # (batch,) int32
    *,
    softcap: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    kvh, _, ps, _ = k_pages.shape
    assert h % kvh == 0
    group = h // kvh
    pages = block_table.shape[1]
    scale = 1.0 / math.sqrt(d)

    q4 = q.reshape(b, kvh, group, d)
    body = functools.partial(
        _paged_body, pages=pages, ps=ps, scale=scale, softcap=softcap
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block_table, start, lengths
        grid=(b, kvh, pages),
        in_specs=[
            pl.BlockSpec(
                (1, 1, group, d), lambda bi, hi, pi, tbl, st, ln: (bi, hi, 0, 0)
            ),
            # the paged gather: the page each step streams is whatever
            # the (prefetched) block table says — index map as crossbar
            pl.BlockSpec(
                (1, 1, ps, d), lambda bi, hi, pi, tbl, st, ln: (hi, tbl[bi, pi], 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, ps, d), lambda bi, hi, pi, tbl, st, ln: (hi, tbl[bi, pi], 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, d), lambda bi, hi, pi, tbl, st, ln: (bi, hi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),  # running max
            pltpu.VMEM((group, 1), jnp.float32),  # running denominator
            pltpu.VMEM((group, d), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        block_table.astype(jnp.int32), start.astype(jnp.int32),
        lengths.astype(jnp.int32), q4, k_pages, v_pages,
    )
    return out.reshape(b, h, d)


# ---------------------------------------------------------------------------
# chunked-prefill supertile kernel (s >= 1, int8 fused dequant)
# ---------------------------------------------------------------------------


def _prefill_body(
    table_ref, start_ref, len_ref, q_ref, k_ref, v_ref, *rest,
    pages: int, ps: int, qc: int, group: int, scale: float,
    softcap: float | None, quant: bool,
):
    if quant:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    o_ref, m_ref, l_ref, acc_ref = rest
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    pi = pl.program_id(3)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[bi]
    q0 = start_ref[bi] + qi * qc  # absolute position of the chunk's row 0

    # a page is dead for this chunk when it starts past the sequence's
    # valid tokens (null-page table tail) OR past the chunk's last query
    # position (causality): either way every score is masked, so skip
    # the MXU work — the supertile analogue of the decode kernel's
    # length gate
    @pl.when((pi * ps < length) & (pi * ps <= q0 + qc - 1))
    def _compute():
        rows = qc * group
        q = q_ref[0, :, 0].reshape(rows, -1)  # (qc*group, d)
        k = k_ref[0, 0]  # (ps, d)
        v = v_ref[0, 0]
        if quant:
            # dequant-on-gather, mirroring the reference backend's
            # numerics exactly: int8 * bf16 scale in fp32, rounded back
            # to bf16 before the attention contractions
            k = (k.astype(jnp.float32)
                 * ks_ref[0, 0].astype(jnp.float32)).astype(jnp.bfloat16)
            v = (v.astype(jnp.float32)
                 * vs_ref[0, 0].astype(jnp.float32)).astype(jnp.bfloat16)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # causal masking vs. the true query positions: row r*group + g
        # is query token qi*qc + r at absolute position q0 + r (bucket
        # padding puts rows past ``length`` here too — they attend to
        # the whole valid sequence and are discarded upstream)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 0) // group
        kpos = pi * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
        s = jnp.where((kpos < length) & (kpos <= qpos), s, NEG_INF)

        m_prev = m_ref[...]  # (rows, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    @pl.when(pi == pages - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0] = (acc_ref[...] / l).reshape(qc, group, -1).astype(o_ref.dtype)


def paged_attention_prefill(
    q: jax.Array,  # (batch, s, n_heads, head_dim) — s query tokens/seq
    k_pages: jax.Array,  # (kv_heads, num_pages, page_size, head_dim)
    v_pages: jax.Array,
    block_table: jax.Array,  # (batch, pages_per_seq) int32
    start: jax.Array,  # (batch,) int32 — absolute position of query token 0
    lengths: jax.Array,  # (batch,) int32 — valid tokens incl. the new ones
    *,
    k_scale: jax.Array | None = None,  # (kvh, P, ps, 1) — int8 page pools
    v_scale: jax.Array | None = None,
    softcap: float | None = None,
    qc: int | None = None,  # query-chunk rows (autotuned; default: all of s)
    interpret: bool = False,
) -> jax.Array:
    """Chunked-prefill paged attention: supertile B-reuse over KV pages.

    Grid ``(batch, kv_heads, q_chunks, pages)`` with the page axis
    sequential: each grid step DMAs ONE K/V page (via the prefetched
    block table, exactly like the decode kernel) and multicasts it to
    the ``qc * group`` query rows of the current chunk, whose running
    softmax state lives in VMEM scratch across page steps.  ``s`` is
    zero-padded up to a multiple of ``qc`` (padded rows land past
    ``lengths`` and are discarded by the caller, same contract as the
    reference backend).  int8 pools pass ``k_scale``/``v_scale`` and the
    gather dequantises in-kernel — no separate dequant pass over HBM.
    """
    b, s, h, d = q.shape
    kvh, _, ps, _ = k_pages.shape
    assert h % kvh == 0
    group = h // kvh
    pages = block_table.shape[1]
    scale = 1.0 / math.sqrt(d)
    quant = k_scale is not None
    qc = min(qc or s, s)
    s_pad = -(-s // qc) * qc
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))

    q5 = q.reshape(b, s_pad, kvh, group, d)
    body = functools.partial(
        _prefill_body, pages=pages, ps=ps, qc=qc, group=group, scale=scale,
        softcap=softcap, quant=quant,
    )
    q_spec = pl.BlockSpec(
        (1, qc, 1, group, d),
        lambda bi, hi, qi, pi, tbl, st, ln: (bi, qi, hi, 0, 0),
    )
    page_spec = pl.BlockSpec(
        (1, 1, ps, d), lambda bi, hi, qi, pi, tbl, st, ln: (hi, tbl[bi, pi], 0, 0)
    )
    in_specs = [q_spec, page_spec, page_spec]
    arrays = [q5, k_pages, v_pages]
    if quant:
        scale_spec = pl.BlockSpec(
            (1, 1, ps, 1),
            lambda bi, hi, qi, pi, tbl, st, ln: (hi, tbl[bi, pi], 0, 0),
        )
        in_specs += [scale_spec, scale_spec]
        arrays += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block_table, start, lengths
        grid=(b, kvh, s_pad // qc, pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((qc * group, 1), jnp.float32),  # running max
            pltpu.VMEM((qc * group, 1), jnp.float32),  # running denominator
            pltpu.VMEM((qc * group, d), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s_pad, kvh, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        block_table.astype(jnp.int32), start.astype(jnp.int32),
        lengths.astype(jnp.int32), *arrays,
    )
    return out.reshape(b, s_pad, h, d)[:, :s]
