"""Plain reference for the Qwen2 architecture (Qwen1.5 configurations).

Written from the published description (Hugging Face ``Qwen2ForCausalLM``)
in straightforward ``jax.numpy``: RMSNorm, grouped-query attention with
q/k/v biases and rotary embeddings (rotate-half form), a causal softmax,
a SwiGLU MLP, tied or untied output head.  No kernel, cache or batching,
and nothing imported from the system under test.  The float32 path
computes every product in float32 at matmul precision ``highest``.

Departures, each forced by comparing with the program on its own
weights: the weights are the tree the benchmark hands the program
(:func:`make_params`), whose norms store ``scale`` with the weight read
as ``1 + scale``; and the weights are drawn like Hugging Face's
initialisation (normal, std ``initializer_range``), except that biases
and norm scales are drawn too (std ``initializer_range``) so that their
paths are checked.

The control path (``mode="fp8"``) is the same forward with the inputs of
every product rounded to float8 e4m3 under a per-tensor scale, the
nearest precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _dims(cfg: dict) -> tuple[int, int, int, int, int, int, int]:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return (d, int(cfg["num_hidden_layers"]), h,
            int(cfg["num_key_value_heads"]), d // h,
            int(cfg["intermediate_size"]), int(cfg["vocab_size"]))


def param_shapes(cfg: dict) -> dict:
    """{path: (shape, dtype)} of the weight tree, in the layout the
    system under test takes (one stage of ``L`` stacked blocks)."""
    d, n_layers, h, kv, hd, f, v = _dims(cfg)
    bf, L = jnp.bfloat16, n_layers
    out = {
        "embed/table": ((v, d), bf),
        "stage0/b0/norm1/scale": ((L, d), F32),
        "stage0/b0/attn/wq": ((L, d, h, hd), bf),
        "stage0/b0/attn/wk": ((L, d, kv, hd), bf),
        "stage0/b0/attn/wv": ((L, d, kv, hd), bf),
        "stage0/b0/attn/wo": ((L, h, hd, d), bf),
        "stage0/b0/attn/bq": ((L, h, hd), bf),
        "stage0/b0/attn/bk": ((L, kv, hd), bf),
        "stage0/b0/attn/bv": ((L, kv, hd), bf),
        "stage0/b0/norm2/scale": ((L, d), F32),
        "stage0/b0/mlp/w_gate": ((L, d, f), bf),
        "stage0/b0/mlp/w_in": ((L, d, f), bf),
        "stage0/b0/mlp/w_out": ((L, f, d), bf),
        "final_norm/scale": ((d,), F32),
    }
    if not cfg["tie_word_embeddings"]:
        out["unembed/w"] = ((d, v), bf)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        *parents, last = path.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


@functools.lru_cache(maxsize=None)
def _maker(shapes: tuple, std: float):
    @jax.jit
    def make(lo, hi):
        base = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        flat = {}
        for path, shape, dtype in shapes:
            k = jax.random.fold_in(base, zlib.crc32(path.encode()))
            flat[path] = (std * jax.random.normal(k, shape, F32)).astype(dtype)
        return _nest(flat)

    return make


def make_params(cfg: dict, seed: int) -> dict:
    """The weights for ``seed``, made on the default device in one jitted
    call, in the dtypes they are served in."""
    shapes = tuple(sorted((p, s, jnp.dtype(t).name)
                          for p, (s, t) in param_shapes(cfg).items()))
    make = _maker(shapes, float(cfg["initializer_range"]))
    # unsigned, so that seeds from 2**31 up fit the 32-bit argument
    return make(np.uint32(seed % 2**32), np.uint32(seed // 2**32 % 2**32))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _q8(x):
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, b, spec: str, mode: str):
    a, b = a.astype(F32), b.astype(F32)
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def _rope(x, pos, theta):
    """Rotate-half rotary embedding.  x: (T, heads, hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(params, cfg: dict, tokens, mode: str = "f32"):
    """Final-norm hidden states (T, d) for one sequence ``tokens`` (T,)."""
    d, _, h, kv, hd, _, _ = _dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    t = tokens.shape[0]
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    x = params["embed"]["table"][tokens].astype(F32)

    def layer(x, p):
        a = p["attn"]
        y = _rmsnorm(x, p["norm1"]["scale"], eps)
        q = _mm(y, a["wq"], "td,dnh->tnh", mode) + a["bq"].astype(F32)
        k = _mm(y, a["wk"], "td,dnh->tnh", mode) + a["bk"].astype(F32)
        v = _mm(y, a["wv"], "td,dnh->tnh", mode) + a["bv"].astype(F32)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        s = _mm(q, k, "tnh,snh->nts", mode) / jnp.sqrt(F32(hd))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = _mm(jax.nn.softmax(s, axis=-1), v, "nts,snh->tnh", mode)
        x = x + _mm(o, a["wo"], "tnh,nhd->td", mode)
        m = p["mlp"]
        y = _rmsnorm(x, p["norm2"]["scale"], eps)
        g = jax.nn.silu(_mm(y, m["w_gate"], "td,df->tf", mode))
        u = _mm(y, m["w_in"], "td,df->tf", mode)
        return x + _mm(g * u, m["w_out"], "tf,fd->td", mode), None

    x, _ = jax.lax.scan(layer, x, params["stage0"]["b0"])
    return _rmsnorm(x, params["final_norm"]["scale"], eps)


def _head(params, cfg: dict):
    if cfg["tie_word_embeddings"]:
        return params["embed"]["table"].T
    return params["unembed"]["w"]


def _logits(params, cfg: dict, x, mode: str):
    return _mm(x, _head(params, cfg), "td,dv->tv", mode)


# positions whose logits are formed at once (bounds the (rows, vocab) block)
_ROWS = 256


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _gaps(params, tokens, targets, *, cfg_key):
    cfg = dict(cfg_key)
    x = hidden(params, cfg, tokens)

    def block(args):
        xb, tb = args
        lg = _logits(params, cfg, xb, "f32")
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0]

    t = tokens.shape[0]
    out = jax.lax.map(block, (x.reshape(t // _ROWS, _ROWS, -1),
                              targets.reshape(t // _ROWS, _ROWS)))
    return out.reshape(t)


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _control_gaps(params, tokens, *, cfg_key):
    cfg = dict(cfg_key)
    x = hidden(params, cfg, tokens)
    x8 = hidden(params, cfg, tokens, "fp8")

    def block(args):
        xb, x8b = args
        lg = _logits(params, cfg, xb, "f32")
        pick = jnp.argmax(_logits(params, cfg, x8b, "fp8"), -1)
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]

    t = tokens.shape[0]
    shp = (t // _ROWS, _ROWS, -1)
    return jax.lax.map(block, (x.reshape(shp), x8.reshape(shp))).reshape(t)


def _key(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def token_gaps(params, cfg: dict, tokens, targets):
    """For each position p of ``tokens`` (T,), how far the float32
    reference's logit of ``targets[p]`` (the token that followed p) lies
    below its best logit there.  T must be a multiple of 256."""
    return _gaps(params, tokens, targets, cfg_key=_key(cfg))


def control_gaps(params, cfg: dict, tokens):
    """For each position, the float32 reference's gap of the token that
    the float8 control puts first."""
    return _control_gaps(params, tokens, cfg_key=_key(cfg))
