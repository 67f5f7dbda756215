"""Invariant auditor + per-page fingerprints for the paged serving stack.

The page pool is the serving stack's multicast fabric: one physical page
fanned out to N consumers by refcount.  That sharing is also the failure
amplifier — a leaked refcount strands capacity forever, a corrupted
shared page poisons every request that matches the prefix covering it.
This module is the detection layer:

* :func:`check_pool` (surfaced as ``PagePool.check()``) — structural
  audit of the pool: free-list disjointness, refcount/free-list
  consistency, null-page sanity (every shard's), and — given the current *holders*
  (every live page-id chain: running slots, prefix-tree nodes,
  in-flight match refs) — an exact cross-count of every page's refcount
  against who actually holds it.  A rejected admission, a preemption, a
  quarantine must all leave this audit green; the chaos suite runs it
  after every step.
* :class:`PageFingerprints` — optional (``kv_guard``) cheap content
  checksums: one fp32 reduction over the whole pool per record/verify
  call, indexed by page id.  Recorded when a chain enters the prefix
  tree and verified **at the sharing point** (``PrefixCache.match`` hit)
  and on preemption **swap-in**, so corruption of a multicast-shared
  chain is caught before it fans out to a new consumer — the engine
  quarantines that chain (evict + re-prefill cold) instead of letting
  it poison every request that shares the prefix.

The checksum is a deterministic jnp reduction (same compiled program +
same bytes = same sum), not a cryptographic hash: it is a tripwire for
bit flips and mis-writes, sized so the guard's decode-path overhead
stays under the bench gate's 5% budget.
"""
from __future__ import annotations

from collections import Counter
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class GuardViolation(AssertionError):
    """An audited invariant does not hold.  Subclasses AssertionError so
    test suites treat it as a failed assertion, with a message naming
    the page and the counts that disagree."""


def check_pool(pool, holders: Iterable[Sequence[int]] | None = None) -> None:
    """Audit ``pool``'s structural invariants; raise :class:`GuardViolation`.

    Always checked:

    * **free-list disjointness** — no duplicate ids on the free list,
      and no free page with a live refcount;
    * **refcount/free-list consistency** — a non-null page has
      refcount 0 iff it sits on the free list (a page in neither place
      is leaked capacity; a page in both is a double grant waiting to
      happen); no negative refcounts;
    * **null-page sanity** — no shard's null page is ever on the free
      list or refcounted, and the pool's in_use/free accounting adds up.

    With ``holders`` (an iterable of page-id chains — each occurrence of
    a page id in any chain is one expected reference): every page's
    refcount must equal exactly the number of chains holding it — the
    multicast fanout cross-count.
    """
    free = pool.free_ids()
    free_set = set(free)
    if len(free_set) != len(free):
        dupes = [p for p, c in Counter(free).items() if c > 1]
        raise GuardViolation(f"free list holds duplicate page ids: {dupes}")
    for s, shard_free in enumerate(pool._free):
        stray = [pid for pid in shard_free if pool.shard_of(pid) != s]
        if stray:
            raise GuardViolation(
                f"shard {s} free list holds pages owned by another shard: "
                f"{stray} — per-shard containment violated"
            )
    nulls = range(0, pool.num_pages, pool.block)
    for null in nulls:
        if null in free_set:
            raise GuardViolation(f"null page {null} is on the free list")
        if pool._ref[null] != 0:
            raise GuardViolation(
                f"null page {null} has refcount {pool._ref[null]} (must stay 0)"
            )
    for pid in range(pool.num_pages):
        if pool.is_null(pid):
            continue
        ref = pool._ref[pid]
        if ref < 0:
            raise GuardViolation(f"page {pid}: negative refcount {ref}")
        if (ref == 0) != (pid in free_set):
            state = "free-listed" if pid in free_set else "leaked (in neither place)"
            raise GuardViolation(
                f"page {pid}: refcount {ref} but {state} — refcount 0 and "
                f"free-list membership must coincide"
            )
    if pool.in_use + pool.free_pages != pool.usable_pages:
        raise GuardViolation(
            f"pool accounting: in_use {pool.in_use} + free {pool.free_pages} "
            f"!= {pool.usable_pages} usable pages"
        )
    if holders is None:
        return
    expected: Counter[int] = Counter()
    for chain in holders:
        expected.update(chain)
    for null in nulls:
        if expected.get(null):
            raise GuardViolation(
                f"a holder chain references the null page {null}")
    for pid in range(pool.num_pages):
        if pool.is_null(pid):
            continue
        if pool._ref[pid] != expected.get(pid, 0):
            raise GuardViolation(
                f"page {pid}: refcount {pool._ref[pid]} != {expected.get(pid, 0)} "
                f"holder references — a reference was leaked or dropped"
            )


# ---------------------------------------------------------------------------
# per-page content fingerprints
# ---------------------------------------------------------------------------


def _page_axis_sums(leaf: jax.Array) -> jax.Array:
    """Per-page |sum| of one stacked page-pool leaf (..., P at axis 2, ...):
    reduce every axis except the page axis."""
    x = jnp.abs(leaf.astype(jnp.float32))
    axes = tuple(i for i in range(x.ndim) if i != 2)
    return jnp.sum(x, axis=axes)


class PageFingerprints:
    """Content checksums for pool pages, keyed by page id.

    ``record(caches, page_ids)`` snapshots the named pages' checksums;
    ``verify(caches, page_ids)`` returns the ids whose bytes no longer
    match.  One jitted whole-pool reduction per call — page chains are
    recorded/verified at admission and swap boundaries, never inside the
    decode hot loop."""

    def __init__(self):
        self._fp: dict[int, float] = {}
        self._sums = jax.jit(
            lambda caches: sum(
                _page_axis_sums(leaf) for leaf in jax.tree.leaves(caches)
            )
        )

    def _checksums(self, caches, page_ids: Sequence[int]) -> dict[int, float]:
        sums = np.asarray(self._sums(caches))
        return {int(pid): float(sums[pid]) for pid in page_ids}

    def record(self, caches, page_ids: Sequence[int]) -> None:
        self._fp.update(self._checksums(caches, page_ids))

    def forget(self, page_ids: Sequence[int]) -> None:
        for pid in page_ids:
            self._fp.pop(int(pid), None)

    def verify(self, caches, page_ids: Sequence[int]) -> list[int]:
        """Ids in ``page_ids`` with a recorded fingerprint that no longer
        matches the live bytes (unrecorded pages are skipped — only a
        chain that was fingerprinted can be audited)."""
        got = self._checksums(caches, page_ids)
        return [
            pid for pid, s in got.items()
            if pid in self._fp and self._fp[pid] != s
        ]


def blob_checksum(data) -> float:
    """Host-side checksum of a preemption swap blob (a tree of np/jnp
    arrays): recorded at swap-out, verified before swap-in scatters the
    blob back into the pool."""
    return float(
        sum(np.abs(np.asarray(leaf, np.float32)).sum()
            for leaf in jax.tree.leaves(data))
    )
