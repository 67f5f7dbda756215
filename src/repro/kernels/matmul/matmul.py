"""Occamy-schedule matmul as a Pallas TPU kernel (paper fig. 3d, adapted).

The paper's schedule: every cluster owns an 8x256 row block of C, reuses
its A block from L1, and the B column tile is *multicast* to all clusters
— fetched from the LLC exactly once per tile instead of once per cluster.

TPU adaptation (HBM -> VMEM plays the LLC -> L1 role):

* ``schedule="mcast"``  — grid (N/bn, K/bk): the A *column panel* (M, bk)
  and B tile (bk, bn) are fetched once per grid step; the B tile is then
  consumed by **all** M/8 row blocks resident in VMEM (the temporal
  analogue of the spatial multicast — one HBM fetch serves every "cluster").
  B HBM traffic: K/bk * N/bn tiles (paper: "load B once, broadcast").
* ``schedule="tiled"``   — grid (M/gm, N/bn, K/bk) with ``gm`` a multi-row
  *supertile*: the B tile is fetched once per supertile and reused across
  all gm/8 row blocks inside it — the spatial analogue of the paper's
  *group-level* multicast (LLC -> group leader -> clusters).  B HBM
  traffic is (M/gm) x instead of the unicast (M/bm) x, and — unlike the
  flat mcast schedule — VMEM holds only a (gm, bn) panel, so M is
  unbounded.  Pallas double-buffers the streamed A/B blocks against the
  MXU automatically (the ``arbitrary`` K axis pipelines), which plays the
  role of the paper's double-buffered LLC tile pipeline.
* ``schedule="unicast"`` — classic (M/bm, N/bn, K/bk) grid: the B tile is
  re-fetched from HBM for every row block i, i.e. (M/bm) x more B traffic
  — the multiple-unicast baseline.

All share one accumulator-in-VMEM kernel body; fp32 accumulation,
MXU-aligned tiles (multiples of 8x128; 128x128 defaults).  The tiled
schedule additionally fuses the epilogue (bias + activation + downcast)
into the flush step, saving the extra HBM round trip a separate epilogue
launch would cost.

See ``repro.kernels.autotune`` for how block sizes are chosen and
``repro.core.occamy.OccamySystem.kernel_schedule_analogy`` for the
mapping back to the paper's hardware hierarchy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_ACTIVATIONS = {
    "none": lambda x: x,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "silu": jax.nn.silu,
    "sigmoid": jax.nn.sigmoid,  # RG-LRU gates fuse their sigmoid here
}


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _body(a_ref, b_ref, o_ref, acc_ref, *, k_axis: int, k_steps: int):
    """Shared body: acc += A_blk @ B_blk (fp32); flush on the last k step."""
    @pl.when(pl.program_id(k_axis) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(k_axis) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_mcast(
    a: jax.Array,
    b: jax.Array,
    *,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with the multicast schedule: grid (N/bn, K/bk).

    The full-M A panel and the B tile live in VMEM per step; one B fetch
    serves all row blocks (the hw-multicast analogue).  Requires
    M * bk and M * bn panels to fit VMEM — for the paper's 256x256 tile
    (M=256, fp32) the working set is ~0.5 MB.

    Non-divisible shapes are zero-padded to block multiples (exact) and
    the output sliced back.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    mp, kp, np_ = _round_up(m, 8), _round_up(k, bk), _round_up(n, bn)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    out = _mcast_call(a, b, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n] if (mp, np_) != (m, n) else out


def _mcast_call(a, b, *, bn, bk, interpret):
    (m, k), n = a.shape, b.shape[1]
    k_steps = pl.cdiv(k, bk)
    grid = (pl.cdiv(n, bn), k_steps)
    return pl.pallas_call(
        functools.partial(_body, k_axis=1, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, bk), lambda j, kk: (0, kk)),  # A panel: all rows
            pl.BlockSpec((bk, bn), lambda j, kk: (kk, j)),  # B tile: ONE fetch
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, b)


def _tiled_body(*refs, k_steps: int, activation: str, has_bias: bool):
    """Supertile body: acc += A_blk @ B_blk; fused epilogue on the flush."""
    if has_bias:
        a_ref, b_ref, bias_ref, o_ref, acc_ref = refs
    else:
        a_ref, b_ref, o_ref, acc_ref = refs
        bias_ref = None

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        acc = acc_ref[...]
        if bias_ref is not None:
            acc = acc + bias_ref[...]  # (1, bn) broadcasts over the supertile
        acc = _ACTIVATIONS[activation](acc)
        o_ref[...] = acc.astype(o_ref.dtype)


def matmul_mcast_tiled(
    a: jax.Array,
    b: jax.Array,
    bias: jax.Array | None = None,
    *,
    gm: int = 1024,
    bn: int = 128,
    bk: int = 128,
    activation: str = "none",
    out_dtype: jnp.dtype | None = None,
    interpret: bool = False,
) -> jax.Array:
    """C = act(A @ B + bias) with the two-level multicast schedule.

    Grid (M/gm, N/bn, K/bk): ``gm`` is a multi-row-block supertile — the
    B tile is fetched from HBM once per supertile and reused by all gm/8
    row blocks inside it (the group-level multicast of the paper's
    hierarchy).  Unlike :func:`matmul_mcast` only a (gm, bn) panel lives
    in VMEM, so M is unbounded; B HBM traffic is ceil(M/gm) x the ideal
    single fetch instead of the unicast ceil(M/bm) x.

    Non-divisible shapes are zero-padded to block multiples (exact: zero
    rows/cols contribute nothing to the dot) and the output sliced back.
    The epilogue — ``bias`` add (shape (N,)), ``activation`` (one of
    %s) and the ``out_dtype`` downcast — runs fused in the flush step.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown activation: {activation!r}")
    out_dtype = jnp.dtype(out_dtype if out_dtype is not None else a.dtype)

    # Clamp the supertile to the (8-aligned) M extent, then pad every
    # operand to block multiples.
    gm = max(8, min(_round_up(gm, 8), _round_up(m, 8)))
    mp, kp, np_ = _round_up(m, gm), _round_up(k, bk), _round_up(n, bn)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    k_steps = kp // bk
    grid = (mp // gm, np_ // bn, k_steps)

    in_specs = [
        pl.BlockSpec((gm, bk), lambda i, j, kk: (i, kk)),  # A supertile panel
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),  # B: 1 fetch / supertile
    ]
    operands = [a, b]
    if bias is not None:
        assert bias.shape == (n,), bias.shape
        bias2d = jnp.pad(bias, (0, np_ - n)).reshape(1, np_).astype(jnp.float32)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(bias2d)

    out = pl.pallas_call(
        functools.partial(
            _tiled_body,
            k_steps=k_steps,
            activation=activation,
            has_bias=bias is not None,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((gm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((gm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*operands)
    return out[:m, :n] if (mp, np_) != (m, n) else out


if matmul_mcast_tiled.__doc__:  # absent under python -OO
    matmul_mcast_tiled.__doc__ %= ", ".join(sorted(_ACTIVATIONS))


def matmul_unicast(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with the classic (multiple-unicast) schedule:
    grid (M/bm, N/bn, K/bk) — B tiles re-fetched for every row block.

    Non-divisible shapes are zero-padded to block multiples (exact) and
    the output sliced back."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    out = _unicast_call(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n] if (mp, np_) != (m, n) else out


def _unicast_call(a, b, *, bm, bn, bk, interpret):
    (m, k), n = a.shape, b.shape[1]
    k_steps = pl.cdiv(k, bk)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), k_steps)
    return pl.pallas_call(
        functools.partial(_body, k_axis=2, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, b)


def hbm_traffic_model(m: int, n: int, k: int, *, bm: int, bn: int, bk: int,
                      gm: int | None = None,
                      dtype_bytes: int = 4) -> dict[str, float]:
    """Analytical HBM byte counts for the schedules (the OI story).

    mcast:   B read once per (j, kk) tile; A panel re-read per j.
    tiled:   B re-read once per *supertile* (gm rows) — the hierarchical
             middle ground; pass ``gm`` to include it.
    unicast: B re-read per row block i (the paper's multiple-unicast).

    Per-schedule B traffic is also exposed as ``<name>_b_bytes`` so the
    reuse hierarchy (mcast <= tiled <= unicast) can be asserted directly.
    """
    a_bytes, b_bytes, c_bytes = (m * k, k * n, m * n)
    j_steps, i_steps = -(-n // bn), -(-m // bm)
    schedules = {
        "mcast": {
            "a": a_bytes * j_steps,  # A panel streamed once per output column
            "b": b_bytes,  # multicast: ONE fetch per B tile
            "c": c_bytes,
        },
        "unicast": {
            "a": a_bytes * j_steps,
            "b": b_bytes * i_steps,  # re-fetched per row block
            "c": c_bytes,
        },
    }
    if gm is not None:
        schedules["tiled"] = {
            "a": a_bytes * j_steps,
            "b": b_bytes * -(-m // gm),  # one fetch per supertile
            "c": c_bytes,
        }
    flops = 2.0 * m * n * k
    out = {}
    for name, t in schedules.items():
        total = sum(t.values()) * dtype_bytes
        out[f"{name}_bytes"] = total
        out[f"{name}_b_bytes"] = t["b"] * dtype_bytes
        out[f"{name}_oi"] = flops / total
    out["oi_ratio"] = out["mcast_oi"] / out["unicast_oi"]
    return out
