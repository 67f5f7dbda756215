"""Pure-jnp oracle for paged attention: gather pages, then attend.

Deliberately mirrors the dense decode path's math in
``nn.attention._attend`` (same einsum forms, fp32 logits, probs cast to
the value dtype before the PV contraction) so a paged serving run and
the dense ring-buffer fallback produce **identical** token streams —
that parity is CI-gated by the serve smoke job.

Also the home of the **dequant-on-gather hook**: int8 page pools pass
per-(page, slot, head) scales and the gather dequantises K/V on the way
into the attention math (`nn.kvquant` semantics), so the quantised page
path needs no separate attention implementation.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -2.0**30


def write_rows(pages: jax.Array, new: jax.Array, page_ids, rows, layer):
    """Reference page write: ``new`` (b, s, G, W) into layer ``layer``
    of the stacked pool (L, G, P, ps, W) at ``(page_ids, rows)`` (b, s)
    — an XLA scatter."""
    layer = jnp.asarray(layer, jnp.int32).reshape(())
    return pages.at[layer, :, page_ids, rows].set(new.astype(pages.dtype))


def gather_pages(pages: jax.Array, block_table: jax.Array) -> jax.Array:
    """(kvh, P, ps, d) pages + (b, n) table -> (b, n*ps, kvh, d) — the
    dense-cache layout, key position = page order * page_size + slot."""
    kvh, _, ps, d = pages.shape
    b, n = block_table.shape
    g = pages[:, block_table]  # (kvh, b, n, ps, d)
    return g.transpose(1, 2, 3, 0, 4).reshape(b, n * ps, kvh, d)


def paged_attention_ref(
    q: jax.Array,  # (b, s, h, d) — s query tokens at positions start..start+s-1
    k_pages: jax.Array,  # (L, G, P, ps, W) — every layer's pool
    v_pages: jax.Array,
    block_table: jax.Array,  # (b, n) int32
    start: jax.Array,  # (b,) int32 — absolute position of query token 0
    lengths: jax.Array,  # (b,) int32 — valid tokens incl. the new ones
    layer: jax.Array | int,  # which layer of the stack
    *,
    softcap: float | None = None,
    k_scale: jax.Array | None = None,  # (L, kvh, P, ps, 1) — int8 page pools
    v_scale: jax.Array | None = None,
) -> jax.Array:
    b, s, h, d = q.shape
    at = functools.partial(jax.lax.dynamic_index_in_dim,
                           index=jnp.asarray(layer, jnp.int32), axis=0,
                           keepdims=False)
    k = gather_pages(at(k_pages), block_table)
    v = gather_pages(at(v_pages), block_table)
    if k_scale is not None:
        k = (k.astype(jnp.float32)
             * gather_pages(at(k_scale), block_table).astype(jnp.float32)
             ).astype(jnp.bfloat16)
        v = (v.astype(jnp.float32)
             * gather_pages(at(v_scale), block_table).astype(jnp.float32)
             ).astype(jnp.bfloat16)
    # lane-packed pools hold W // d kv heads per row, side by side
    t = k.shape[1]
    k = k.reshape(b, t, -1, d)
    v = v.reshape(b, t, -1, d)
    kvh = k.shape[2]
    group = h // kvh

    q5 = q.reshape(b, s, kvh, group, d)
    logits = jnp.einsum("bskgh,btkh->bkgst", q5, k).astype(jnp.float32)
    logits = logits / math.sqrt(d)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)

    # explicit query positions (start + j, NOT lengths - s + j: bucketed
    # suffix prefills pad s past the true token count, and padded query
    # rows sit beyond ``lengths`` — their outputs are discarded upstream)
    qpos = start[:, None] + jnp.arange(s)[None, :]  # (b, s)
    kpos = jnp.arange(t)
    kp = kpos[None, None, None, None, :]
    mask = (kp <= qpos[:, None, None, :, None]) \
        & (kp < lengths[:, None, None, None, None])
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(b, s, h, d)
