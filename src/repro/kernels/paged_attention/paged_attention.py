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

Layout: one pool array per K and V holds every layer, ``(L, G, P, ps,
W)`` (layers, lane groups, pages, page rows, lanes); the page axis is
axis 2.  ``W`` is ``head_dim``, or 128 with ``128 // head_dim`` kv heads
side by side in each row where the head is narrower than a lane row
(``nn.attention.packed_heads``); kv head ``g * pack + i`` lives in lanes
``[i * head_dim, (i + 1) * head_dim)`` of group ``g``.  The kernels
take the whole stack and the layer index, which rides the
scalar-prefetch channel with the block table, so a decode step reads
each layer's pages where they lie: the K/V index maps are
``(layer, group, table[b, p], 0, 0)``.  With packed heads each query
head is spread into its kv head's lanes with zeros elsewhere
(:func:`spread_heads`), so one (rows, 128) x (128, ps) product gives
every head its own scores, and the output is read back per head
(:func:`collect_heads`).

Decode grid ``(batch, groups, pages_per_seq)`` with the page axis
sequential ("arbitrary"): the running-softmax state (m, l, acc) for the
``pack * n_heads / kv_heads`` query rows of one lane group lives in
VMEM scratch across page steps, exactly like the flash kernel's kv
axis.  The **block table rides the scalar-prefetch channel**
(``PrefetchScalarGridSpec``): K/V index maps read ``table[b, p]`` to
pick the page each grid step DMAs, so the gather happens in the
pipeline's address generation — no materialised contiguous KV copy.
Pages past a sequence's length still occupy grid steps (the table pads
with the null page 0) but skip all compute via ``pl.when``; the ragged
tail inside the last page is masked positionally.

:func:`page_write` stores new K/V rows into the stacked pools in place:
a grid over (row, lane group, page touched), each step rewriting one
page block of an aliased output, so no step copies, slices or relays
the pool.

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
    table_ref, start_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
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
        q = q_ref[0, 0]  # (rows, W)
        k = k_ref[0, 0, 0]  # (ps, W)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # ragged tail + causality: key position pi*ps + j must be
        # within the sequence and not past the query token
        kpos = pi * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((kpos < length) & (kpos <= qpos), s, NEG_INF)

        m_prev = m_ref[...]  # (rows, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0, 0, 0],
            preferred_element_type=jnp.float32,
        )

    @pl.when(pi == pages - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def spread_heads(q: jax.Array, pack: int) -> jax.Array:
    """(..., G * pack, group, d) query heads -> (..., G, pack * group,
    pack * d): the query of kv head ``g * pack + i`` sits in lanes
    ``[i * d, (i + 1) * d)``, zeros in the other heads' lanes, so its
    product with a packed page row is that head's score alone."""
    if pack == 1:
        return q
    *lead, kvh, group, d = q.shape
    q = q.reshape(*lead, kvh // pack, pack, group, 1, d)
    eye = jnp.eye(pack, dtype=bool)[:, None, :, None]  # (i, 1, j, 1)
    z = jnp.where(eye, q, jnp.zeros((), q.dtype))
    return z.reshape(*lead, kvh // pack, pack * group, pack * d)


def collect_heads(o: jax.Array, pack: int, d: int) -> jax.Array:
    """Inverse of :func:`spread_heads` on the attention output: (...,
    G, pack * group, pack * d) -> (..., G * pack, group, d), each row
    read from its own head's lanes."""
    *lead, groups, rows, _ = o.shape
    o = o.reshape(*lead, groups, pack, rows // pack, pack, d)
    if pack == 1:
        return o[..., 0, :].reshape(*lead, groups, rows, d)
    eye = jnp.eye(pack, dtype=bool)[:, None, :, None]
    o = jnp.where(eye, o, jnp.zeros((), o.dtype)).sum(axis=-2, dtype=o.dtype)
    return o.reshape(*lead, groups * pack, rows // pack, d)


def paged_attention_decode(
    q: jax.Array,  # (batch, n_heads, head_dim)
    k_pages: jax.Array,  # (L, G, num_pages, page_size, W)
    v_pages: jax.Array,
    block_table: jax.Array,  # (batch, pages_per_seq) int32
    start: jax.Array,  # (batch,) int32 — the decode token's position
    lengths: jax.Array,  # (batch,) int32
    layer: jax.Array | int,  # which layer of the stack
    *,
    softcap: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    _, groups, _, ps, lanes = k_pages.shape
    pack = lanes // d
    kvh = groups * pack
    assert h % kvh == 0
    rows = pack * (h // kvh)
    pages = block_table.shape[1]
    scale = 1.0 / math.sqrt(d)

    q4 = spread_heads(q.reshape(b, kvh, h // kvh, d), pack)
    body = functools.partial(
        _paged_body, pages=pages, ps=ps, scale=scale, softcap=softcap
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # block_table, start, lengths, layer
        grid=(b, groups, pages),
        in_specs=[
            pl.BlockSpec(
                (1, 1, rows, lanes),
                lambda bi, hi, pi, tbl, st, ln, ly: (bi, hi, 0, 0),
            ),
            # the paged gather: the page each step streams is whatever
            # the (prefetched) block table says — index map as crossbar
            pl.BlockSpec(
                (1, 1, 1, ps, lanes),
                lambda bi, hi, pi, tbl, st, ln, ly: (ly[0], hi, tbl[bi, pi], 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, 1, ps, lanes),
                lambda bi, hi, pi, tbl, st, ln, ly: (ly[0], hi, tbl[bi, pi], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, rows, lanes), lambda bi, hi, pi, tbl, st, ln, ly: (bi, hi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),  # running max
            pltpu.VMEM((rows, 1), jnp.float32),  # running denominator
            pltpu.VMEM((rows, lanes), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, groups, rows, lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="paged_decode",
        interpret=interpret,
    )(
        block_table.astype(jnp.int32), start.astype(jnp.int32),
        lengths.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        q4, k_pages, v_pages,
    )
    return collect_heads(out, pack, d).reshape(b, h, d)


# ---------------------------------------------------------------------------
# chunked-prefill supertile kernel (s >= 1, int8 fused dequant)
# ---------------------------------------------------------------------------


def _prefill_body(
    table_ref, start_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
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
        q = q_ref[0, :, 0].reshape(rows, -1)  # (qc*group, W)
        k = k_ref[0, 0, 0]  # (ps, W)
        v = v_ref[0, 0, 0]
        if quant:
            # dequant-on-gather, mirroring the reference backend's
            # numerics exactly: int8 * bf16 scale in fp32, rounded back
            # to bf16 before the attention contractions
            k = (k.astype(jnp.float32)
                 * ks_ref[0, 0, 0].astype(jnp.float32)).astype(jnp.bfloat16)
            v = (v.astype(jnp.float32)
                 * vs_ref[0, 0, 0].astype(jnp.float32)).astype(jnp.bfloat16)
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
    k_pages: jax.Array,  # (L, G, num_pages, page_size, W)
    v_pages: jax.Array,
    block_table: jax.Array,  # (batch, pages_per_seq) int32
    start: jax.Array,  # (batch,) int32 — absolute position of query token 0
    lengths: jax.Array,  # (batch,) int32 — valid tokens incl. the new ones
    layer: jax.Array | int,  # which layer of the stack
    *,
    k_scale: jax.Array | None = None,  # (L, kvh, P, ps, 1) — int8 page pools
    v_scale: jax.Array | None = None,
    softcap: float | None = None,
    qc: int | None = None,  # query-chunk rows (autotuned; default: all of s)
    interpret: bool = False,
) -> jax.Array:
    """Chunked-prefill paged attention: supertile B-reuse over KV pages.

    Grid ``(batch, groups, q_chunks, pages)`` with the page axis
    sequential: each grid step DMAs ONE K/V page (via the prefetched
    block table, exactly like the decode kernel) and multicasts it to
    the ``qc * rows`` query rows of the current chunk, whose running
    softmax state lives in VMEM scratch across page steps.  ``s`` is
    zero-padded up to a multiple of ``qc`` (padded rows land past
    ``lengths`` and are discarded by the caller, same contract as the
    reference backend).  int8 pools (never lane-packed) pass
    ``k_scale``/``v_scale`` and the gather dequantises in-kernel — no
    separate dequant pass over HBM.
    """
    b, s, h, d = q.shape
    quant = k_scale is not None
    _, groups, _, ps, lanes = k_pages.shape
    pack = lanes // d
    kvh = groups * pack
    assert h % kvh == 0
    rows = pack * (h // kvh)  # query rows per token and lane group
    pages = block_table.shape[1]
    scale = 1.0 / math.sqrt(d)
    qc = min(qc or s, s)
    s_pad = -(-s // qc) * qc
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))

    q5 = spread_heads(q.reshape(b, s_pad, kvh, h // kvh, d), pack)
    body = functools.partial(
        _prefill_body, pages=pages, ps=ps, qc=qc, group=rows, scale=scale,
        softcap=softcap, quant=quant,
    )
    q_spec = pl.BlockSpec(
        (1, qc, 1, rows, lanes),
        lambda bi, hi, qi, pi, tbl, st, ln, ly: (bi, qi, hi, 0, 0),
    )
    page_spec = pl.BlockSpec(
        (1, 1, 1, ps, lanes),
        lambda bi, hi, qi, pi, tbl, st, ln, ly: (ly[0], hi, tbl[bi, pi], 0, 0),
    )
    in_specs = [q_spec, page_spec, page_spec]
    arrays = [q5, k_pages, v_pages]
    if quant:
        scale_spec = pl.BlockSpec(
            (1, 1, 1, ps, 1),
            lambda bi, hi, qi, pi, tbl, st, ln, ly: (ly[0], hi, tbl[bi, pi], 0, 0),
        )
        in_specs += [scale_spec, scale_spec]
        arrays += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # block_table, start, lengths, layer
        grid=(b, groups, s_pad // qc, pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((qc * rows, 1), jnp.float32),  # running max
            pltpu.VMEM((qc * rows, 1), jnp.float32),  # running denominator
            pltpu.VMEM((qc * rows, lanes), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s_pad, groups, rows, lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        name="pallas_prefill",
        interpret=interpret,
    )(
        block_table.astype(jnp.int32), start.astype(jnp.int32),
        lengths.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        *arrays,
    )
    return collect_heads(out, pack, d).reshape(b, s_pad, h, d)[:, :s]


# ---------------------------------------------------------------------------
# in-place page write
# ---------------------------------------------------------------------------


def touched_pages(page_ids: jax.Array, page_size: int):
    """The pages each row's tokens land in, in order: ``(slot (b, s),
    pages (b, T))``, token ``j`` of row ``b`` going to page
    ``pages[b, slot[b, j]]``.  A row's ids are those of consecutive
    positions, a valid prefix followed by null-page padding, so a row
    touches at most ``T = min(s, (s + ps - 2) // ps + 2)`` pages: the
    pages of its valid run plus the null page.  Unused slots hold the
    null page."""
    b, s = page_ids.shape
    n = min(s, (s + page_size - 2) // page_size + 2)
    first = jnp.ones((b, 1), bool)
    change = jnp.concatenate([first, page_ids[:, 1:] != page_ids[:, :-1]], 1)
    slot = jnp.cumsum(change, axis=1, dtype=jnp.int32) - 1
    pages = jnp.zeros((b, n), jnp.int32).at[
        jnp.arange(b)[:, None], slot].set(page_ids.astype(jnp.int32))
    return slot, pages


def _page_write_body(layer_ref, pages_ref, code_ref, kn_ref, vn_ref,
                     kp_ref, vp_ref, ko_ref, vo_ref, *, ps: int):
    # token j goes to row code[j] - t * ps of the page this step holds;
    # its one-hot (ps, s) selector moves it there through the MXU
    # (exact: one 1.0 per row, the rest 0.0) and every other row keeps
    # the page's bytes
    ti = pl.program_id(2)
    code = code_ref[0]  # (1, s)
    s = code.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (ps, s), 0) + ti * ps
    hit = row == code
    took = jnp.max(hit.astype(jnp.float32), axis=1, keepdims=True) > 0
    for new_ref, old_ref, out_ref in ((kn_ref, kp_ref, ko_ref),
                                      (vn_ref, vp_ref, vo_ref)):
        new = new_ref[0, 0]  # (s, W)
        if s == 1:
            rows = jnp.broadcast_to(new, (ps, new.shape[-1]))
        else:
            prec = (jax.lax.Precision.HIGHEST if new.dtype == jnp.float32
                    else None)
            rows = jnp.dot(hit.astype(new.dtype), new, precision=prec,
                           preferred_element_type=jnp.float32)
        out_ref[0, 0, 0] = jnp.where(took, rows.astype(out_ref.dtype),
                                     old_ref[0, 0, 0])


def page_write(
    k_pages: jax.Array,  # (L, G, P, ps, W)
    v_pages: jax.Array,
    k_new: jax.Array,  # (b, s, G, W) — rows to store
    v_new: jax.Array,
    page_ids: jax.Array,  # (b, s) int32 — page of each row (0 = padding)
    rows: jax.Array,  # (b, s) int32 — row within that page
    layer: jax.Array | int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Store new K/V rows into layer ``layer`` of the stacked pools in
    place.  Grid ``(batch, groups, pages touched)``: each step reads one
    page block of the aliased pool, puts the row's tokens that fall in
    it at their rows, and writes the block back, so no page outside the
    touched ones is read or written and XLA keeps the pool where it is
    (``input_output_aliases``; the caller donates the pools).  Tokens of
    a row must follow :func:`touched_pages`' contract; padded tokens
    land in the null page 0, whose bytes are never attended to."""
    _, groups, _, ps, lanes = k_pages.shape
    b, s = page_ids.shape
    slot, pages = touched_pages(page_ids, ps)
    code = (slot * ps + rows.astype(jnp.int32))[:, None, :]  # (b, 1, s)
    k_new = k_new.transpose(0, 2, 1, 3)  # (b, G, s, W)
    v_new = v_new.transpose(0, 2, 1, 3)
    if s > 1 and s % 16:
        # pad the contraction to the bf16 sublane tile; padded tokens
        # match no row
        pad = 16 - s % 16
        code = jnp.pad(code, ((0, 0), (0, 0), (0, pad)), constant_values=-1)
        k_new = jnp.pad(k_new, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_new = jnp.pad(v_new, ((0, 0), (0, 0), (0, pad), (0, 0)))
        s += pad
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    new_spec = pl.BlockSpec((1, 1, s, lanes),
                            lambda bi, gi, ti, ly, tp: (bi, gi, 0, 0))
    page_spec = pl.BlockSpec(
        (1, 1, 1, ps, lanes),
        lambda bi, gi, ti, ly, tp: (ly[0], gi, tp[bi, ti], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # layer, touched pages
        grid=(b, groups, pages.shape[1]),
        in_specs=[
            pl.BlockSpec((1, 1, s), lambda bi, gi, ti, ly, tp: (bi, 0, 0)),
            new_spec, new_spec, page_spec, page_spec,
        ],
        out_specs=[page_spec, page_spec],
    )
    return pl.pallas_call(
        functools.partial(_page_write_body, ps=ps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        # operands: layer, pages, code, k_new, v_new, k_pages, v_pages
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
        name="page_write",
        interpret=interpret,
    )(layer, pages, code, k_new.astype(k_pages.dtype),
      v_new.astype(v_pages.dtype), k_pages, v_pages)
