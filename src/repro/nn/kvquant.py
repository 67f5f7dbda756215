"""int8-quantised KV cache (decode memory-term optimisation, §Perf).

Decode cells are KV-traffic-bound (e.g. qwen decode_32k: 1.97 ms memory
term vs 10 µs compute).  Storing K/V as int8 with per-(slot, head)
scales halves the dominant HBM traffic; logits error stays below bf16
round-off for typical activations (validated in tests/test_kvquant.py).

Opt-in path: ``build_decode_step(..., kv_dtype="int8")`` swaps the cache
pytree for ``QuantKvCache`` and routes attention through
``quant_decode_attention``; the default bf16 path is untouched.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttnConfig
from repro.nn.attention import (
    KvCache,
    _attend,
    _paged_call,
    _proj_out,
    _qkv,
)


class QuantKvCache(NamedTuple):
    k: jax.Array  # (batch, slots, kv_heads, head_dim) int8
    v: jax.Array  # int8
    k_scale: jax.Array  # (batch, slots, kv_heads, 1) bf16
    v_scale: jax.Array
    pos: jax.Array  # (batch, slots) int32, -1 = empty


def quant_cache_spec(batch: int, slots: int, cfg: AttnConfig):
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    return QuantKvCache(
        k=jax.ShapeDtypeStruct((batch, slots, kv, hd), jnp.int8),
        v=jax.ShapeDtypeStruct((batch, slots, kv, hd), jnp.int8),
        k_scale=jax.ShapeDtypeStruct((batch, slots, kv, 1), jnp.bfloat16),
        v_scale=jax.ShapeDtypeStruct((batch, slots, kv, 1), jnp.bfloat16),
        pos=jax.ShapeDtypeStruct((batch, slots), jnp.int32),
    )


def init_quant_cache(batch: int, slots: int, cfg: AttnConfig):
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    return QuantKvCache(
        k=jnp.zeros((batch, slots, kv, hd), jnp.int8),
        v=jnp.zeros((batch, slots, kv, hd), jnp.int8),
        k_scale=jnp.zeros((batch, slots, kv, 1), jnp.bfloat16),
        v_scale=jnp.zeros((batch, slots, kv, 1), jnp.bfloat16),
        pos=jnp.full((batch, slots), -1, jnp.int32),
    )


def quantize_kv(x: jax.Array):
    """(…, hd) -> int8 values + per-vector scale."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def quantize_cache(cache: KvCache) -> QuantKvCache:
    kq, ks = quantize_kv(cache.k)
    vq, vs = quantize_kv(cache.v)
    return QuantKvCache(k=kq, v=vq, k_scale=ks, v_scale=vs, pos=cache.pos)


def quant_decode_attention(
    params,
    x,
    cache: QuantKvCache,
    cfg: AttnConfig,
    *,
    index: jax.Array,
    window: int | None = None,
):
    """decode_attention against an int8 cache (same semantics as the
    bf16 path: position-explicit ring buffer)."""
    b, s_new, _ = x.shape
    slots = cache.k.shape[1]
    index = jnp.asarray(index)
    if index.ndim == 0:
        index = index[None]
    positions = index[:, None] + jnp.arange(s_new)[None, :]
    positions = jnp.broadcast_to(positions, (b, s_new))
    q, k_new, v_new = _qkv(params, x, cfg, positions)

    kq_new, ks_new = quantize_kv(k_new)
    vq_new, vs_new = quantize_kv(v_new)
    write_slots = (positions % slots).astype(jnp.int32)
    bidx = jnp.arange(b)[:, None]
    kq = cache.k.at[bidx, write_slots].set(kq_new)
    vq = cache.v.at[bidx, write_slots].set(vq_new)
    ks = cache.k_scale.at[bidx, write_slots].set(ks_new)
    vs = cache.v_scale.at[bidx, write_slots].set(vs_new)
    pos = cache.pos.at[bidx, write_slots].set(positions)

    k = dequantize_kv(kq, ks)
    v = dequantize_kv(vq, vs)
    qp = positions[:, None, None, :, None]
    kp = pos[:, None, None, None, :]
    mask = (kp >= 0) & (kp <= qp)
    if window is not None:
        mask &= qp - kp < window
    o = _attend(q, k, v, mask, cfg)
    new_cache = QuantKvCache(k=kq, v=vq, k_scale=ks, v_scale=vs, pos=pos)
    return _proj_out(params, o, cfg), new_cache


class QuantPagedKvCache(NamedTuple):
    """int8 page pool (`nn.attention.PagedKvCache` with per-(page, slot,
    head) scales): halves the dominant decode HBM term for paged serving
    too.  Both paged-attention backends dequantise on gather — the
    pallas supertile kernel fuses the int8 * scale dequant into the page
    DMA consumption (scales ride the same block-table index maps), the
    reference backend dequantises the gathered copy."""

    k_pages: jax.Array  # (kv_heads, num_pages, page_size, head_dim) int8
    v_pages: jax.Array
    k_scale: jax.Array  # (kv_heads, num_pages, page_size, 1) bf16
    v_scale: jax.Array


def init_quant_paged_cache(num_pages: int, page_size: int, cfg: AttnConfig):
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    return QuantPagedKvCache(
        k_pages=jnp.zeros((kv, num_pages, page_size, hd), jnp.int8),
        v_pages=jnp.zeros((kv, num_pages, page_size, hd), jnp.int8),
        k_scale=jnp.zeros((kv, num_pages, page_size, 1), jnp.bfloat16),
        v_scale=jnp.zeros((kv, num_pages, page_size, 1), jnp.bfloat16),
    )


def quant_paged_decode_attention(
    params,
    x,
    cache: QuantPagedKvCache,
    cfg: AttnConfig,
    *,
    index: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    layer: jax.Array | None = None,
    window: int | None = None,
    page_axis: str | None = None,
):
    """`attention.paged_decode_attention` against int8 pages: new K/V
    rows are quantised on the way in, the attention gather dequantises
    on the way out (fused in-kernel on the pallas supertile schedule,
    on the gathered copy in the reference backend)."""
    return _paged_call(params, x, cache, cfg, index=index,
                       block_table=block_table, lengths=lengths, layer=layer,
                       window=window, page_axis=page_axis,
                       quantize=quantize_kv)


def cache_bytes(cache) -> int:
    """Total cache bytes (for the memory-term comparison)."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
