"""Decoder-only LM covering the dense / MoE / SSM / hybrid / VLM families.

A model is a sequence of *stages*, each a ``lax.scan`` over stacked
super-block parameters (see ``repro.configs.base``).  Three execution
modes share the same parameter tree:

* ``forward``     — full-sequence training forward (no caches),
* ``prefill``     — full-sequence forward that also builds decode caches,
* ``decode_step`` — single-token (or few-token) step against caches.

Caches mirror the stage structure: for every stage a pytree with leading
dim = repeats, holding per-super-block entries (``KvCache`` for attention
— ring-buffered for local windows — ``RglruState`` / ``SsdState`` for the
recurrent mixers).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro import kernels
from repro.configs.base import BlockDef, ModelConfig
from repro.nn import attention as attn_mod
from repro.nn import kvquant
from repro.nn import moe as moe_mod
from repro.nn import rglru as rglru_mod
from repro.nn import ssd as ssd_mod
from repro.nn.module import (
    dense,
    dense_spec,
    embed,
    embed_spec,
    layernorm,
    layernorm_spec,
    rmsnorm,
    rmsnorm_spec,
    softcap,
    unembed,
)
from repro.nn.spec import ParamSpec, abstract_params, init_params, stacked


# ---------------------------------------------------------------------------
# spec construction
# ---------------------------------------------------------------------------


def _norm_spec(cfg: ModelConfig):
    return rmsnorm_spec(cfg.d_model) if cfg.norm == "rmsnorm" else layernorm_spec(cfg.d_model)


def _norm(cfg: ModelConfig, params, x):
    return rmsnorm(params, x) if cfg.norm == "rmsnorm" else layernorm(params, x)


def mlp_spec(cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    spec = {
        "w_in": ParamSpec((d, f), axes=("embed", "ff")),
        "w_out": ParamSpec((f, d), axes=("ff", "embed")),
    }
    if cfg.glu:
        spec["w_gate"] = ParamSpec((d, f), axes=("embed", "ff"))
    return spec


def mlp(params, x, cfg: ModelConfig):
    """Dispatched MLP: the activation rides the matmul epilogue (one
    fused kernel per projection on TPU instead of matmul + HBM round
    trip + elementwise launch)."""
    if cfg.glu:
        h = kernels.linear(x, params["w_gate"], activation=cfg.act) \
            * kernels.linear(x, params["w_in"])
    else:
        h = kernels.linear(x, params["w_in"], activation=cfg.act)
    return kernels.linear(h, params["w_out"])


def block_spec(cfg: ModelConfig, bd: BlockDef):
    spec: dict[str, Any] = {"norm1": _norm_spec(cfg)}
    if bd.mixer == "attn":
        spec["attn"] = attn_mod.attn_spec(cfg.d_model, cfg.attn)
    elif bd.mixer == "rglru":
        spec["rglru"] = rglru_mod.rglru_spec(cfg.d_model, cfg.rglru)
    elif bd.mixer == "ssd":
        spec["ssd"] = ssd_mod.ssd_spec(cfg.d_model, cfg.ssm)
    else:
        raise ValueError(bd.mixer)
    if cfg.post_block_norm:
        spec["norm1_post"] = _norm_spec(cfg)
    if bd.ff == "mlp":
        spec["norm2"] = _norm_spec(cfg)
        spec["mlp"] = mlp_spec(cfg)
    elif bd.ff == "moe":
        spec["norm2"] = _norm_spec(cfg)
        spec["moe"] = moe_mod.moe_spec(cfg.d_model, cfg.moe, glu=cfg.glu)
    if bd.ff != "none" and cfg.post_block_norm:
        spec["norm2_post"] = _norm_spec(cfg)
    return spec


def model_spec(cfg: ModelConfig):
    spec: dict[str, Any] = {"embed": embed_spec(cfg.vocab, cfg.d_model)}
    if cfg.attn is not None and cfg.attn.learned_pos:
        spec["pos"] = {
            "table": ParamSpec((cfg.max_position, cfg.d_model), axes=(None, "embed"),
                               init="normal", scale=0.02)
        }
    if cfg.frontend:
        spec["frontend_proj"] = dense_spec(
            cfg.frontend_dim, cfg.d_model, axes=(None, "embed")
        )
    for i, (pattern, repeats) in enumerate(cfg.stages):
        sb = {f"b{j}": block_spec(cfg, bd) for j, bd in enumerate(pattern)}
        spec[f"stage{i}"] = stacked(sb, repeats)
    spec["final_norm"] = _norm_spec(cfg)
    if not cfg.tie_embeddings:
        spec["unembed"] = {
            "w": ParamSpec((cfg.d_model, cfg.vocab), axes=("embed", "vocab"))
        }
    return spec


def init(cfg: ModelConfig, key: jax.Array):
    return init_params(model_spec(cfg), key)


def abstract(cfg: ModelConfig):
    return abstract_params(model_spec(cfg))


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def _block_cache_spec(cfg: ModelConfig, bd: BlockDef, batch: int, cache_len: int, abstract_=True):
    maker = _abstract_cache if abstract_ else _concrete_cache
    return maker(cfg, bd, batch, cache_len)


def _slots(bd: BlockDef, cache_len: int) -> int:
    return min(bd.window, cache_len) if bd.window else cache_len


def _abstract_cache(cfg, bd, batch, cache_len, kv_dtype="bf16"):
    if bd.mixer == "attn":
        if kv_dtype == "int8":
            return kvquant.quant_cache_spec(batch, _slots(bd, cache_len), cfg.attn)
        return attn_mod.cache_spec(batch, _slots(bd, cache_len), cfg.attn)
    if bd.mixer == "rglru":
        return rglru_mod.rglru_state_spec(batch, cfg.d_model, cfg.rglru)
    return ssd_mod.ssd_state_spec(batch, cfg.d_model, cfg.ssm)


def _concrete_cache(cfg, bd, batch, cache_len, kv_dtype="bf16"):
    if bd.mixer == "attn":
        if kv_dtype == "int8":
            return kvquant.init_quant_cache(batch, _slots(bd, cache_len), cfg.attn)
        return attn_mod.init_cache(batch, _slots(bd, cache_len), cfg.attn)
    if bd.mixer == "rglru":
        return rglru_mod.init_rglru_state(batch, cfg.d_model, cfg.rglru)
    return ssd_mod.init_ssd_state(batch, cfg.d_model, cfg.ssm)


def _stack_tree(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _stack_spec_tree(trees):
    def stk(*xs):
        return jax.ShapeDtypeStruct((len(xs), *xs[0].shape), xs[0].dtype)

    return jax.tree.map(stk, *trees, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def cache_spec(cfg: ModelConfig, batch: int, cache_len: int, kv_dtype: str = "bf16"):
    """Abstract decode-cache tree (ShapeDtypeStructs, no allocation)."""
    out = {}
    for i, (pattern, repeats) in enumerate(cfg.stages):
        sb = {
            f"b{j}": _abstract_cache(cfg, bd, batch, cache_len, kv_dtype)
            for j, bd in enumerate(pattern)
        }
        out[f"stage{i}"] = _stack_spec_tree([sb] * repeats)
    return out


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, kv_dtype: str = "bf16"):
    out = {}
    for i, (pattern, repeats) in enumerate(cfg.stages):
        sb = {
            f"b{j}": _concrete_cache(cfg, bd, batch, cache_len, kv_dtype)
            for j, bd in enumerate(pattern)
        }
        out[f"stage{i}"] = _stack_tree([sb] * repeats)
    return out


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     kv_dtype: str = "bf16"):
    """Paged decode-cache tree: one page pool per attention layer, all
    indexed by the same host-managed block tables (one allocation covers
    the stack).  Paged serving covers global-attention blocks only —
    recurrent mixers and local windows keep the dense path."""
    out = {}
    for i, (pattern, repeats) in enumerate(cfg.stages):
        sb = {}
        for j, bd in enumerate(pattern):
            # MoE is excluded too: expert capacity scales with the
            # *padded* call length (nn/moe.py), so the bucketed /
            # suffix-only prefills this cache implies would route —
            # and drop — real tokens differently than the dense path
            if bd.mixer != "attn" or bd.window is not None or bd.ff == "moe":
                raise ValueError(
                    f"paged KV serving needs global-attention non-MoE blocks; "
                    f"stage {i} block {j} has mixer={bd.mixer!r}, "
                    f"window={bd.window!r}, ff={bd.ff!r} — serve this arch "
                    f"with the dense fallback (--kv dense)"
                )
            maker = (
                kvquant.init_quant_paged_cache if kv_dtype == "int8"
                else attn_mod.init_paged_cache
            )
            sb[f"b{j}"] = maker(num_pages, page_size, cfg.attn)
        out[f"stage{i}"] = _stack_tree([sb] * repeats)
    return out


_PAGED_CACHES = (attn_mod.PagedKvCache, kvquant.QuantPagedKvCache)
_DENSE_CACHES = (attn_mod.KvCache, kvquant.QuantKvCache)


def mask_cache_after(caches, length):
    """Mark every cache position at or past ``length`` empty (pos=-1) —
    the fixup that makes right-padded bucket prefills exact: the padded
    tail's K/V rows stay in the ring but can never be attended to."""
    def fix(c):
        if isinstance(c, _DENSE_CACHES):
            return c._replace(pos=jnp.where(c.pos >= length, -1, c.pos))
        return c

    return jax.tree.map(fix, caches, is_leaf=lambda x: isinstance(x, _DENSE_CACHES))


def mask_cache_rows_after(caches, lengths):
    """Per-row :func:`mask_cache_after`: ``lengths`` is (batch,) and row
    ``b``'s cache positions at or past ``lengths[b]`` are marked empty.

    The speculative-decoding draft cache needs this after every
    verify-accept round: the draft wrote K/V for all k proposed tokens,
    but only the accepted prefix is real history — rejected rows must
    become unattendable without touching the other batch rows."""
    lengths = jnp.asarray(lengths, jnp.int32)

    def fix(c):
        if isinstance(c, _DENSE_CACHES):
            # pos is (..., batch, slots); (batch, 1) broadcasts from the
            # right regardless of leading stage-stack dims
            return c._replace(
                pos=jnp.where(c.pos >= lengths[:, None], -1, c.pos))
        return c

    return jax.tree.map(fix, caches, is_leaf=lambda x: isinstance(x, _DENSE_CACHES))


def prefill_to_pages(dense_caches, paged_caches, block_table, length, *,
                     page_axis=None):
    """Scatter a batch-1 dense prefill cache into the page pools.

    ``block_table``: (pages,) page ids covering positions
    ``[0, pages * page_size)``; rows past ``length`` (bucket padding) go
    to the null page.  Cold paged prefills run the exact same
    ``lm.prefill`` as the dense path and then land here, so the page
    bytes are bit-identical to the dense fallback's ring bytes.  (The
    prefix-hit *suffix* path never comes through here — it writes its
    pages directly via ``decode_step``, one call per prefill chunk.)
    ``page_axis``: as in :func:`decode_step` — ids are global, the pools
    this device's block; pages another device owns go to its null page."""
    if page_axis is not None:
        block = jax.tree.leaves(paged_caches)[0].shape[2]
        block_table = attn_mod.local_page_ids(block_table, page_axis, block)[0]
    flat_d, _ = jax.tree_util.tree_flatten(
        dense_caches, is_leaf=lambda x: isinstance(x, _DENSE_CACHES)
    )
    flat_p, treedef = jax.tree_util.tree_flatten(
        paged_caches, is_leaf=lambda x: isinstance(x, _PAGED_CACHES)
    )
    out = [
        _scatter_dense_into_pages(d, p, block_table, length)
        for d, p in zip(flat_d, flat_p)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def _scatter_dense_into_pages(dense_c, paged_c, table, length):
    """dense_c: stacked KvCache (repeats, 1, s_pad, kv, hd);
    paged_c: stacked paged cache (repeats, G, P, ps, W)."""
    ps = paged_c.k_pages.shape[3]
    s_pad = dense_c.k.shape[2]
    pos = jnp.arange(s_pad)
    valid = pos < length
    pidx = jnp.clip(pos // ps, 0, table.shape[0] - 1)
    ids = jnp.where(valid, table[pidx], 0)  # null-page sink for padding
    rows = jnp.where(valid, pos % ps, 0)
    if isinstance(paged_c, kvquant.QuantPagedKvCache):
        k = dense_c.k[:, 0].transpose(0, 2, 1, 3)  # (repeats, kv, s_pad, hd)
        v = dense_c.v[:, 0].transpose(0, 2, 1, 3)
        kq, ks = kvquant.quantize_kv(k)
        vq, vs = kvquant.quantize_kv(v)
        return kvquant.QuantPagedKvCache(
            k_pages=paged_c.k_pages.at[:, :, ids, rows].set(kq),
            v_pages=paged_c.v_pages.at[:, :, ids, rows].set(vq),
            k_scale=paged_c.k_scale.at[:, :, ids, rows].set(ks),
            v_scale=paged_c.v_scale.at[:, :, ids, rows].set(vs),
        )
    # one in-place page write per layer: the prompt's rows as one batch
    # row, whole pages but the last
    groups, lanes = paged_c.k_pages.shape[1], paged_c.k_pages.shape[-1]

    def write_layer(pools, layer_kv):
        layer, k, v = layer_kv
        new = [a.reshape(1, s_pad, groups, lanes) for a in (k, v)]
        return kernels.op("page_write")(
            *pools, *new, ids[None], rows[None], layer), None

    pools, _ = jax.lax.scan(
        write_layer, tuple(paged_c),
        (jnp.arange(dense_c.k.shape[0]), dense_c.k[:, 0], dense_c.v[:, 0]))
    return attn_mod.PagedKvCache(*pools)


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------


def _apply_block(params, bd: BlockDef, cfg: ModelConfig, x, *, mode: str,
                 cache=None, index=None, cache_slots=None,
                 block_table=None, lengths=None, page_axis=None, layer=None):
    """Returns (x, new_cache, aux_loss).  ``layer``: a paged ``cache``
    is the stack of every layer's pool and this block's index in it."""
    aux = jnp.zeros((), jnp.float32)
    h = _norm(cfg, params["norm1"], x)
    new_cache = cache
    if bd.mixer == "attn":
        if mode == "decode":
            if isinstance(cache, _PAGED_CACHES):
                paged_fn = (
                    kvquant.quant_paged_decode_attention
                    if isinstance(cache, kvquant.QuantPagedKvCache)
                    else attn_mod.paged_decode_attention
                )
                m, new_cache = paged_fn(
                    params["attn"], h, cache, cfg.attn, index=index,
                    block_table=block_table, lengths=lengths, layer=layer,
                    window=bd.window, page_axis=page_axis,
                )
            else:
                decode_fn = (
                    kvquant.quant_decode_attention
                    if isinstance(cache, kvquant.QuantKvCache)
                    else attn_mod.decode_attention
                )
                m, new_cache = decode_fn(
                    params["attn"], h, cache, cfg.attn, index=index,
                    window=bd.window,
                )
        else:
            m = attn_mod.attention(
                params["attn"], h, cfg.attn, window=bd.window, causal=True
            )
            if mode == "prefill":
                new_cache = _kv_from_full(params["attn"], h, cfg, bd, cache_slots)
    elif bd.mixer == "rglru":
        if mode == "decode":
            m, new_cache = rglru_mod.rglru_step(params["rglru"], h, cache, cfg.rglru)
        else:
            m, st = rglru_mod.rglru(params["rglru"], h, cfg.rglru)
            new_cache = st if mode == "prefill" else None
    else:  # ssd
        if mode == "decode":
            m, new_cache = ssd_mod.ssd_step(params["ssd"], h, cache, cfg.ssm)
        else:
            m, st = ssd_mod.ssd(params["ssd"], h, cfg.ssm)
            new_cache = st if mode == "prefill" else None
    if cfg.post_block_norm:
        m = _norm(cfg, params["norm1_post"], m)
    x = x + m

    if bd.ff != "none":
        h = _norm(cfg, params["norm2"], x)
        if bd.ff == "mlp":
            f = mlp(params["mlp"], h, cfg)
        else:
            f, aux = moe_mod.moe(params["moe"], h, cfg.moe, act=cfg.act, glu=cfg.glu)
        if cfg.post_block_norm:
            f = _norm(cfg, params["norm2_post"], f)
        x = x + f
    return x, new_cache, aux


def _kv_from_full(params, h, cfg: ModelConfig, bd: BlockDef, cache_slots=None):
    """Build a decode cache from a prefill forward (positions 0..s-1).

    ``cache_slots`` sizes the ring for the decode phase (>= s for full
    attention that must keep every prefilled position visible)."""
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    _, k, v = attn_mod._qkv(params, h, cfg.attn, positions)
    slots = _slots(bd, max(cache_slots or s, s))
    if slots >= s:
        pad = slots - s
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
    else:
        # ring layout: slot = position % slots; keep the last ``slots``
        idx = (jnp.arange(s - slots, s) // 1)  # absolute positions kept
        ring = idx % slots
        k_r = jnp.zeros((b, slots, *k.shape[2:]), k.dtype).at[:, ring].set(k[:, s - slots :])
        v_r = jnp.zeros((b, slots, *v.shape[2:]), v.dtype).at[:, ring].set(v[:, s - slots :])
        pos = jnp.full((b, slots), -1, jnp.int32).at[:, ring].set(
            jnp.broadcast_to(idx[None, :], (b, slots))
        )
        k, v = k_r, v_r
    return attn_mod.KvCache(k=k, v=v, pos=pos.astype(jnp.int32))


# ---------------------------------------------------------------------------
# stage execution (scan over stacked super-blocks)
# ---------------------------------------------------------------------------


def _run_stage(params_stage, pattern, cfg: ModelConfig, x, *, mode, caches=None,
               index=None, remat=False, cache_slots=None,
               block_table=None, lengths=None, page_axis=None):
    # In decode, the K/V page pools ride the scan's carry whole, with the
    # layer counter: each layer writes its rows in place and its kernel
    # reads its pages from the stack, so no step slices out a layer's
    # pool or builds a new stack from the slices.  Every other cache
    # (dense, recurrent, int8 pools) goes through as scan inputs and
    # outputs, one layer's slice per step.
    pools = {}
    if mode == "decode" and caches is not None:
        pools = {k: c for k, c in caches.items()
                 if isinstance(c, attn_mod.PagedKvCache)}
        caches = {k: c for k, c in caches.items() if k not in pools} or None

    def super_block(carry, xs):
        x, aux, pools, layer = carry
        p_sb, cache_sb = xs
        pools = dict(pools)
        new_caches = {}
        for j, bd in enumerate(pattern):
            key = f"b{j}"
            if key in pools:
                c, at = pools[key], layer
            else:
                c = cache_sb.get(key) if cache_sb is not None else None
                at = None
            x, nc, a = _apply_block(
                p_sb[key], bd, cfg, x, mode=mode, cache=c, index=index,
                cache_slots=cache_slots, block_table=block_table,
                lengths=lengths, page_axis=page_axis, layer=at,
            )
            if key in pools:
                pools[key] = nc
            elif nc is not None:
                new_caches[key] = nc
            aux = aux + a
        return (x, aux, pools, layer + 1), (new_caches or None)

    if remat:
        super_block = jax.checkpoint(super_block)

    carry = (x, jnp.zeros((), jnp.float32), pools, jnp.zeros((), jnp.int32))
    (x, aux, pools, _), new_caches = jax.lax.scan(
        super_block, carry, (params_stage, caches))
    if pools:
        new_caches = {**(new_caches or {}), **pools}
    return x, aux, new_caches


def _embed_inputs(params, cfg: ModelConfig, tokens, frontend_embeds=None):
    x = embed(params["embed"], tokens)
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * jnp.sqrt(float(cfg.d_model))).astype(x.dtype)
    if frontend_embeds is not None:
        fe = dense(params["frontend_proj"], frontend_embeds).astype(x.dtype)
        x = jnp.concatenate([fe, x], axis=1)
    if cfg.attn is not None and cfg.attn.learned_pos:
        s = x.shape[1]
        x = x + params["pos"]["table"][:s][None].astype(x.dtype)
    return x


def _logits(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        out = unembed(params["embed"], x)
    else:
        out = kernels.linear(
            x.astype(jnp.float32), params["unembed"]["w"].astype(jnp.float32)
        )
    return softcap(out, cfg.final_softcap)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def forward(params, cfg: ModelConfig, tokens, *, frontend_embeds=None, remat=False):
    """Training forward: (batch, seq) tokens -> (batch, seq, vocab) logits."""
    x = _embed_inputs(params, cfg, tokens, frontend_embeds)
    aux_total = jnp.zeros((), jnp.float32)
    for i, (pattern, _) in enumerate(cfg.stages):
        x, aux, _ = _run_stage(
            params[f"stage{i}"], pattern, cfg, x, mode="train", remat=remat
        )
        aux_total = aux_total + aux
    x = _norm(cfg, params["final_norm"], x)
    return _logits(params, cfg, x), aux_total


def loss_fn(params, cfg: ModelConfig, tokens, labels, *, frontend_embeds=None,
            remat=False, loss_chunk: int | None = 512, aux_weight: float = 0.01):
    """Mean next-token cross entropy (+ MoE aux loss).

    The softmax/CE is computed in sequence chunks so that the fp32 logits
    tensor never materialises at full (batch, seq, vocab) size — with 256k
    vocabs this is the difference between ~250 MB and ~30 GB per device.
    """
    x = _embed_inputs(params, cfg, tokens, frontend_embeds)
    aux_total = jnp.zeros((), jnp.float32)
    for i, (pattern, _) in enumerate(cfg.stages):
        x, aux, _ = _run_stage(
            params[f"stage{i}"], pattern, cfg, x, mode="train", remat=remat
        )
        aux_total = aux_total + aux
    x = _norm(cfg, params["final_norm"], x)

    b, s, d = x.shape
    if loss_chunk is None or s <= loss_chunk:
        ce = _ce(params, cfg, x, labels)
    else:
        n = s // loss_chunk
        xc = x.reshape(b, n, loss_chunk, d).transpose(1, 0, 2, 3)
        lc = labels.reshape(b, n, loss_chunk).transpose(1, 0, 2)

        # checkpoint: recompute the (chunk, vocab) logits in backward
        # instead of saving them (256k-vocab logits dominate temps otherwise)
        @jax.checkpoint
        def chunk_ce(carry, xs):
            xi, li = xs
            return carry + _ce(params, cfg, xi, li) * (1.0 / n), None

        ce, _ = jax.lax.scan(chunk_ce, jnp.zeros((), jnp.float32), (xc, lc))
    return ce + aux_weight * aux_total


def _ce(params, cfg: ModelConfig, x, labels):
    logits = _logits(params, cfg, x)  # fp32
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def prefill(params, cfg: ModelConfig, tokens, *, frontend_embeds=None,
            cache_slots: int | None = None, logit_index=None):
    """Prefill: forward over the prompt -> (last_logits, caches).

    ``cache_slots`` sizes the decode ring buffers (defaults to the prompt
    length; pass the serving cache length to decode past the prompt with
    full attention).  ``logit_index`` (scalar or (batch,), traced) picks
    which position's logits to return instead of the last — the hook
    bucketed serving prefills use: right-pad the prompt to a shared
    length bucket (one compile per bucket, not per prompt length) and
    read the logits at the true last token."""
    x = _embed_inputs(params, cfg, tokens, frontend_embeds)
    caches = {}
    for i, (pattern, _) in enumerate(cfg.stages):
        x, _, stage_cache = _run_stage(
            params[f"stage{i}"], pattern, cfg, x, mode="prefill",
            cache_slots=cache_slots,
        )
        caches[f"stage{i}"] = stage_cache
    x = _norm(cfg, params["final_norm"], x)
    if logit_index is None:
        sel = x[:, -1:, :]
    else:
        li = jnp.asarray(logit_index, jnp.int32)
        if li.ndim == 0:
            li = jnp.broadcast_to(li[None], (x.shape[0],))
        sel = jax.vmap(
            lambda xi, ii: jax.lax.dynamic_slice_in_dim(xi, ii, 1, axis=0)
        )(x, li)
    return _logits(params, cfg, sel), caches


def decode_step(params, cfg: ModelConfig, caches, tokens, index, *,
                block_table=None, lengths=None, page_axis=None):
    """One decode step (or a few — paged suffix prefills pass s_new > 1).

    tokens: (batch, s_new); index: absolute position of the first new
    token (scalar, or (batch,) for ragged continuous batching).  Paged
    caches additionally take the shared ``block_table`` (batch, pages)
    and ``lengths`` (batch,) = valid tokens after this call's writes.
    This is also the chunked-prefill entry point: the serving engine
    splits a long divergent suffix into fixed-size chunks and calls
    this once per chunk (advancing ``index``/``lengths``), which writes
    the same page bytes as one big call — on TPU each multi-token call
    runs the paged-attention supertile kernel.  ``page_axis`` names the
    mesh axis a sharded page pool is split over: the caller runs this
    inside a ``shard_map`` over it, with each device holding one pool
    shard's page block (``nn.attention.write_and_attend``).

    Returns (logits (batch, s_new, vocab), updated caches)."""
    x = _embed_inputs(params, cfg, tokens)
    new_caches = {}
    for i, (pattern, _) in enumerate(cfg.stages):
        x, _, stage_cache = _run_stage(
            params[f"stage{i}"], pattern, cfg, x,
            mode="decode", caches=caches[f"stage{i}"], index=index,
            block_table=block_table, lengths=lengths, page_axis=page_axis,
        )
        new_caches[f"stage{i}"] = stage_cache
    x = _norm(cfg, params["final_norm"], x)
    return _logits(params, cfg, x), new_caches
