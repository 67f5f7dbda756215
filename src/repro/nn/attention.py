"""Grouped-query attention with RoPE, local windows, softcaps, KV caches.

Supports the attention variants of every assigned architecture:
* GQA / MQA / MHA via ``n_kv_heads``              (all archs)
* QKV biases                                      (qwen1.5)
* attention-logit softcapping                     (gemma-2)
* sliding local windows, incl. ring-buffer caches (gemma-2, recurrentgemma)
* learned absolute positions / no RoPE            (whisper)
* bidirectional (encoder) attention               (whisper encoder)

The KV cache is position-explicit: alongside K/V we store the absolute
position of every cache slot (-1 = empty) and build masks by comparing
positions, which makes full caches and ring-buffer (local-window) caches
the same code path.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import kernels
from repro.configs.base import AttnConfig
from repro.nn.memeff import memeff_attention
from repro.nn.module import rope, softcap
from repro.nn.spec import ParamSpec

NEG_INF = -2.0**30  # large-negative in fp32; avoids bf16 overflow surprises


def proj_heads(x, w, bias=None):
    """Headed projection (..., d) @ (d, n, h) -> (..., n, h) through the
    dispatched matmul (the old ``einsum("bsd,dnh->bsnh")`` sites)."""
    return kernels.linear(x, w, bias=bias)


def attn_spec(d_model: int, cfg: AttnConfig):
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    spec = {
        "wq": ParamSpec((d_model, h, hd), axes=("embed", "heads", None)),
        "wk": ParamSpec((d_model, kv, hd), axes=("embed", "kv_heads", None)),
        "wv": ParamSpec((d_model, kv, hd), axes=("embed", "kv_heads", None)),
        "wo": ParamSpec((h, hd, d_model), axes=("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        spec["bq"] = ParamSpec((h, hd), axes=("heads", None), init="zeros")
        spec["bk"] = ParamSpec((kv, hd), axes=("kv_heads", None), init="zeros")
        spec["bv"] = ParamSpec((kv, hd), axes=("kv_heads", None), init="zeros")
    if cfg.out_bias:
        spec["bo"] = ParamSpec((d_model,), axes=("embed",), init="zeros")
    return spec


class KvCache(NamedTuple):
    """Position-explicit KV cache (ring buffer when len < max positions)."""

    k: jax.Array  # (batch, slots, kv_heads, head_dim)
    v: jax.Array  # (batch, slots, kv_heads, head_dim)
    pos: jax.Array  # (batch, slots) int32, -1 = empty


def cache_spec(batch: int, slots: int, cfg: AttnConfig, dtype=jnp.bfloat16):
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    return KvCache(
        k=jax.ShapeDtypeStruct((batch, slots, kv, hd), dtype),
        v=jax.ShapeDtypeStruct((batch, slots, kv, hd), dtype),
        pos=jax.ShapeDtypeStruct((batch, slots), jnp.int32),
    )


def init_cache(batch: int, slots: int, cfg: AttnConfig, dtype=jnp.bfloat16):
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    return KvCache(
        k=jnp.zeros((batch, slots, kv, hd), dtype),
        v=jnp.zeros((batch, slots, kv, hd), dtype),
        pos=jnp.full((batch, slots), -1, jnp.int32),
    )


class PagedKvCache(NamedTuple):
    """Page-pool KV cache: physical pages shared across sequences.

    Position ``p`` of the sequence in batch slot ``b`` lives in page
    ``block_table[b, p // page_size]`` at row ``p % page_size``; the
    block table and per-sequence lengths are *not* part of the cache —
    they are host-managed (``repro.serve``) and passed alongside, shared
    by every layer (one allocation covers the whole stack).  Pages
    referenced by several block tables (shared prefixes) exist once —
    the serving-side multicast."""

    k_pages: jax.Array  # (kv_heads, num_pages, page_size, head_dim)
    v_pages: jax.Array


def paged_cache_spec(num_pages: int, page_size: int, cfg: AttnConfig,
                     dtype=jnp.bfloat16):
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    return PagedKvCache(
        k_pages=jax.ShapeDtypeStruct((kv, num_pages, page_size, hd), dtype),
        v_pages=jax.ShapeDtypeStruct((kv, num_pages, page_size, hd), dtype),
    )


def init_paged_cache(num_pages: int, page_size: int, cfg: AttnConfig,
                     dtype=jnp.bfloat16):
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    return PagedKvCache(
        k_pages=jnp.zeros((kv, num_pages, page_size, hd), dtype),
        v_pages=jnp.zeros((kv, num_pages, page_size, hd), dtype),
    )


def paged_positions(x, index, lengths, page_size: int, n_entries: int):
    """Shared prelude of the paged decode paths: absolute positions of
    the ``s_new`` tokens plus their (page-table slot, in-page row)
    write coordinates.  Positions at or past ``lengths`` (suffix-bucket
    padding, inactive batch slots) are redirected to the **null page 0**
    so a padded write can never land in a page some sequence owns.

    Returns ``(positions (b, s_new), page_slot (b, s_new), row (b, s_new),
    valid (b, s_new))`` — ``page_slot`` still needs the block-table
    lookup (``take_along_axis``) to become a physical page id."""
    b, s_new = x.shape[0], x.shape[1]
    index = jnp.asarray(index)
    if index.ndim == 0:
        index = index[None]
    positions = index[:, None] + jnp.arange(s_new)[None, :]
    positions = jnp.broadcast_to(positions, (b, s_new)).astype(jnp.int32)
    valid = (positions >= 0) & (positions < jnp.asarray(lengths)[:, None])
    page_slot = jnp.clip(positions // page_size, 0, n_entries - 1)
    row = jnp.where(valid, positions % page_size, 0)
    return positions, page_slot, row, valid


def paged_write(pages, values, page_ids, rows):
    """Scatter new K/V rows into their pages: ``pages`` (kvh, P, ps, d),
    ``values`` (b, s, kvh, d), ``page_ids``/``rows`` (b, s)."""
    return pages.at[:, page_ids, rows].set(values.transpose(2, 0, 1, 3))


def local_page_ids(ids, page_axis: str, block: int):
    """Global page ids -> this device's block of a page pool sharded
    evenly over the mesh axis ``page_axis`` (call inside a ``shard_map``
    over it; ``block`` pages per device).  Returns ``(local_ids, mine)``:
    ids another device owns become the local null page 0."""
    base = jax.lax.axis_index(page_axis) * block
    mine = (ids >= base) & (ids < base + block)
    return jnp.where(mine, ids - base, 0), mine


def write_and_attend(q, pages: tuple, new: tuple, block_table, page_ids, rows,
                     start, lengths, *, softcap=None, page_axis: str | None = None):
    """Scatter this call's new rows into the pool arrays, then run the
    ``paged_attention`` op over them.  ``pages`` is the pool's array
    tuple (K, V and, for int8 pools, the K/V scales, in that order) and
    ``new`` the matching per-token rows (b, s, kvh, ·); ``page_ids`` /
    ``rows`` are the write coordinates.  Returns ``(o, pages)``.

    ``page_axis`` names the mesh axis a sharded pool's page axis is
    split over; the caller runs this inside a ``shard_map`` over it, so
    ``pages`` are this device's block (one pool shard, led by its null
    page).  Page ids are translated to the block, the device computes
    only the rows whose pages it owns (a row's pages all live on one
    shard, so its first table entry names the owner; other rows get
    length 0 and write to the null page), and a ``psum`` over the axis
    assembles the rows.  No page array leaves its device."""
    if page_axis is not None:
        block = pages[0].shape[1]
        block_table, mine = local_page_ids(block_table, page_axis, block)
        page_ids = local_page_ids(page_ids, page_axis, block)[0]
        owned = mine[:, 0]
        lengths = jnp.where(owned, lengths, 0)
    pages = tuple(paged_write(a, n, page_ids, rows) for a, n in zip(pages, new))
    k, v, *scales = pages
    o = kernels.op("paged_attention")(
        q, k, v, block_table, start, lengths, *scales, softcap=softcap)
    if page_axis is not None:
        o = jnp.where(owned[:, None, None, None], o, jnp.zeros_like(o))
        o = jax.lax.psum(o, page_axis)
    return o, pages


def paged_decode_attention(
    params,
    x,
    cache: PagedKvCache,
    cfg: AttnConfig,
    *,
    index: jax.Array,
    block_table: jax.Array,  # (b, pages_per_seq) int32
    lengths: jax.Array,  # (b,) int32 — valid tokens AFTER this call's writes
    window: int | None = None,
    page_axis: str | None = None,
):
    """Decode (or prefix-hit suffix prefill) against the page pool.

    ``x``: (b, s_new, d_model); ``index`` is the absolute position of
    the first new token (scalar or (b,)).  The ``s_new`` new tokens are
    written into their block-table pages first, then attention runs over
    all ``lengths`` valid positions through the ``paged_attention``
    kernel op: on TPU, single-token calls dispatch to the pallas decode
    gather kernel and multi-token suffix prefills to the chunked-prefill
    supertile kernel (one K/V page fetch multicast across the q chunk);
    off-TPU both run the reference gather.  Calling this per suffix
    *chunk* (increasing ``index``/``lengths``) leaves page bytes
    identical to one call — the engine's chunked prefill relies on it.
    ``page_axis``: the pool is sharded over that mesh axis and this runs
    inside a ``shard_map`` over it (see :func:`write_and_attend`).
    """
    if window is not None:
        raise NotImplementedError(
            "paged KV serving covers global attention only; local-window "
            "blocks use the dense ring-buffer path"
        )
    ps = cache.k_pages.shape[2]
    lengths = jnp.asarray(lengths, jnp.int32)
    positions, page_slot, rows, valid = paged_positions(
        x, index, lengths, ps, block_table.shape[1]
    )
    q, k_new, v_new = _qkv(params, x, cfg, positions)
    page_ids = jnp.where(
        valid, jnp.take_along_axis(block_table, page_slot, axis=1), 0
    )
    dtype = cache.k_pages.dtype
    o, pages = write_and_attend(
        q, tuple(cache), (k_new.astype(dtype), v_new.astype(dtype)),
        block_table, page_ids, rows, positions[:, 0], lengths,
        softcap=cfg.logit_softcap, page_axis=page_axis,
    )
    return _proj_out(params, o, cfg), PagedKvCache(*pages)


def _qkv(params, x, cfg: AttnConfig, positions):
    q = proj_heads(x, params["wq"], params["bq"] if cfg.qkv_bias else None)
    k = proj_heads(x, params["wk"], params["bk"] if cfg.qkv_bias else None)
    v = proj_heads(x, params["wv"], params["bv"] if cfg.qkv_bias else None)
    if cfg.rope:
        q = rope(q, positions, theta=cfg.rope_theta)
        k = rope(k, positions, theta=cfg.rope_theta)
    return q, k, v


def _gqa_scores(q, k, cfg: AttnConfig):
    """(b, s, h, hd) x (b, t, kv, hd) -> (b, kv, g, s, t) fp32 logits."""
    b, s, h, hd = q.shape
    kv = cfg.n_kv_heads
    g = h // kv
    q = q.reshape(b, s, kv, g, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32)
    logits = logits / math.sqrt(hd)
    return softcap(logits, cfg.logit_softcap)


def _attend(q, k, v, mask, cfg: AttnConfig):
    logits = _gqa_scores(q, k, cfg)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    b, s = q.shape[0], q.shape[1]
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(b, s, cfg.n_heads, cfg.head_dim)


def _proj_out(params, o, cfg: AttnConfig):
    # contracts (heads, head_dim) — the old einsum("bsnh,nhd->bsd")
    return kernels.linear(
        o, params["wo"], contract_dims=2,
        bias=params["bo"] if cfg.out_bias else None,
    )


# ---------------------------------------------------------------------------
# full-sequence attention (training / prefill)
# ---------------------------------------------------------------------------


def attention(
    params,
    x,
    cfg: AttnConfig,
    *,
    positions=None,
    window: int | None = None,
    causal: bool = True,
):
    """Self-attention over a full sequence (blockwise online-softmax —
    O(qc*kc) temps, banded KV for local windows).  x: (b, seq, d_model)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = _qkv(params, x, cfg, positions)
    pos = jnp.broadcast_to(positions, (b, s)).astype(jnp.int32)
    o = memeff_attention(
        q, k, v, pos, pos,
        causal=causal, window=window, softcap=cfg.logit_softcap,
    )
    return _proj_out(params, o, cfg)


def cross_attention(params, x, kv_input, cfg: AttnConfig):
    """Encoder-decoder cross attention (no RoPE on either side)."""
    q = proj_heads(x, params["wq"], params["bq"] if cfg.qkv_bias else None)
    k = proj_heads(kv_input, params["wk"], params["bk"] if cfg.qkv_bias else None)
    v = proj_heads(kv_input, params["wv"], params["bv"] if cfg.qkv_bias else None)
    b, s = x.shape[0], x.shape[1]
    t = kv_input.shape[1]
    qp = jnp.broadcast_to(jnp.arange(s)[None], (b, s)).astype(jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(t)[None], (b, t)).astype(jnp.int32)
    o = memeff_attention(
        q, k, v, qp, kp, causal=False, softcap=cfg.logit_softcap,
    )
    return _proj_out(params, o, cfg)


# ---------------------------------------------------------------------------
# cached decode step
# ---------------------------------------------------------------------------


def decode_attention(
    params,
    x,
    cache: KvCache,
    cfg: AttnConfig,
    *,
    index: jax.Array,
    window: int | None = None,
):
    """One (or a few) decode steps against a KV cache.

    x: (batch, s_new, d_model); ``index`` is the absolute position of the
    first new token — a scalar, or a (batch,) vector for ragged batches
    (continuous batching: every slot at its own position).  The cache is a
    ring buffer over ``slots``; for local windows ``slots`` >= window.
    """
    b, s_new, _ = x.shape
    slots = cache.k.shape[1]
    index = jnp.asarray(index)
    if index.ndim == 0:
        index = index[None]
    positions = index[:, None] + jnp.arange(s_new)[None, :]  # (1|b, s_new)
    positions = jnp.broadcast_to(positions, (b, s_new))
    q, k_new, v_new = _qkv(params, x, cfg, positions)

    # ring-buffer write: slot = position % slots
    write_slots = (positions % slots).astype(jnp.int32)  # (b, s_new)
    bidx = jnp.arange(b)[:, None]
    k = cache.k.at[bidx, write_slots].set(k_new.astype(cache.k.dtype))
    v = cache.v.at[bidx, write_slots].set(v_new.astype(cache.v.dtype))
    pos = cache.pos.at[bidx, write_slots].set(positions)

    qp = positions[:, None, None, :, None]  # (b,1,1,s_new,1)
    kp = pos[:, None, None, None, :]  # (b,1,1,1,slots)
    mask = (kp >= 0) & (kp <= qp)
    if window is not None:
        mask &= qp - kp < window
    o = _attend(q, k, v, mask, cfg)
    return _proj_out(params, o, cfg), KvCache(k=k, v=v, pos=pos)
