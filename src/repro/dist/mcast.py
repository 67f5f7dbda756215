"""Multicast collectives on the TPU fabric (fig. 3b adaptation).

The paper's three B-distribution strategies, expressed as jax
collectives so the compiled HLO exhibits the same cost hierarchy:

* ``unicast`` — the source sends the payload to every receiver
  separately: N-1 ``collective-permute`` ops (the multiple-unicast
  baseline, LLC port serialised);
* ``sw_tree`` — recursive doubling: log2(N) permute rounds (the
  hierarchical software multicast, LLC -> leaders -> groups);
* ``hw``     — one fused collective (psum / all-gather): the XBAR-fork
  hw multicast, a single fabric transaction.

``tests/test_mcast.py`` and the multi-device scenarios assert the
permute counts (N-1 / log2 N / 0) straight from compiled HLO.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

MODES = ("unicast", "sw_tree", "hw")


def _axis(mesh) -> str:
    return "data" if "data" in mesh.axis_names else mesh.axis_names[0]


def _from_source(x_masked: jax.Array, mode: str, axis: str, n: int) -> jax.Array:
    """Deliver device 0's ``x_masked`` (zeros elsewhere) to every device."""
    if mode == "hw":
        return lax.psum(x_masked, axis)
    if mode == "unicast":
        y = x_masked
        for t in range(1, n):  # N-1 separate sends from the source
            y = y + lax.ppermute(x_masked, axis, perm=[(0, t)])
        return y
    if mode == "sw_tree":
        y = x_masked
        k = 1
        while k < n:  # doubling rounds: holders forward to +k
            y = y + lax.ppermute(y, axis, perm=[(i, i + k) for i in range(k)])
            k *= 2
        return y
    raise ValueError(f"unknown mode: {mode!r} (have {MODES})")


def make_broadcast_fn(mesh, shape, dtype, mode: str):
    """f(x): deliver device 0's copy of ``x`` to every device via ``mode``."""
    axis = _axis(mesh)
    n = dict(mesh.shape)[axis]

    def body(x):
        i = lax.axis_index(axis)
        masked = jnp.where(i == 0, x, jnp.zeros_like(x))
        return _from_source(masked, mode, axis, n)

    return jax.shard_map(
        body, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
    )


def make_weight_gather_fn(mesh, shape, dtype, mode: str):
    """f(w): each device contributes its row shard; every device ends with
    the full ``w`` (the FSDP weight-fetch path, per distribution mode)."""
    axis = _axis(mesh)
    n = dict(mesh.shape)[axis]
    assert shape[0] % n == 0, (shape, n)
    rows = shape[0] // n

    def body(w):
        i = lax.axis_index(axis)
        mine = lax.dynamic_slice_in_dim(w, i * rows, rows, 0)
        buf = lax.dynamic_update_slice_in_dim(
            jnp.zeros(shape, w.dtype), mine, i * rows, 0
        )
        if mode == "hw":
            return lax.psum(buf, axis)
        if mode == "sw_tree":
            k = 1
            while k < n:  # recursive doubling: exchange with partner i^k
                buf = buf + lax.ppermute(
                    buf, axis, perm=[(j, j ^ k) for j in range(n)]
                )
                k *= 2
            return buf
        if mode == "unicast":
            acc, cur = buf, buf
            for _ in range(n - 1):  # ring rotation, one hop at a time
                cur = lax.ppermute(cur, axis, perm=[(j, (j + 1) % n) for j in range(n)])
                acc = acc + cur
            return acc
        raise ValueError(f"unknown mode: {mode!r} (have {MODES})")

    return jax.shard_map(
        body, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
    )


def mcast_matmul(x, w, mesh, *, mode: str = "hw"):
    """Row-sharded x @ multicast-distributed w (the paper's kernel story
    on the fabric: one w fetch serves every row shard under ``hw``)."""
    axis = _axis(mesh)
    n = dict(mesh.shape)[axis]

    def body(xs, wf):
        i = lax.axis_index(axis)
        masked = jnp.where(i == 0, wf, jnp.zeros_like(wf))
        wl = _from_source(masked, mode, axis, n)
        return xs @ wl

    f = jax.shard_map(
        body, mesh=mesh, in_specs=(P(axis, None), P()), out_specs=P(axis, None),
        check_vma=False,
    )
    return f(x, w)


def bytes_model(payload_bytes: int, n: int, *,
                per_device: bool = False) -> dict[str, float]:
    """Analytic fabric-byte counts per mode (mirrors core.noc).

    The default is the *link-total* model: bytes crossing any fabric
    link, summed.  For power-of-two ``n`` unicast and sw_tree tie there
    (``sum(2**k, k<log2 n) == n-1`` — the tree moves the same bytes,
    just not serialised through the source's port), so the hierarchy a
    serving deployment feels is the **per-device** one:

    ``per_device=True`` returns the collective bytes each participant
    *sends* — ``(n-1)·P`` / ``ceil(log2 n)·P`` / ``P`` — the multiplier
    the serving engine's ``broadcast_fabric_bytes`` counter uses.
    ``launch.hlo.analyze_compiled`` counts every transfer at both
    endpoints, so its ``collective_bytes`` lands at exactly 2x this
    model in every mode (bench_collective_bytes.py reports predicted
    vs. observed; the mode *hierarchy* is identical).  With one device
    there is no fabric: every mode is 0.
    """
    if per_device:
        if n <= 1:
            return {m: 0.0 for m in MODES}
        return {
            "unicast": float(payload_bytes * (n - 1)),
            "sw_tree": float(payload_bytes * math.ceil(math.log2(n))),
            "hw": float(payload_bytes),
        }
    return {
        "unicast": float(payload_bytes * (n - 1)),
        "sw_tree": float(payload_bytes * sum(2**k for k in range(int(math.log2(n))))),
        "hw": float(payload_bytes),
    }
