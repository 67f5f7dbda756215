"""Paged continuous-batching engine: the serving subsystem's mechanism.

Owns the device side of paged serving and executes the
:class:`~repro.serve.scheduler.Scheduler`'s policy decisions:

* one **page pool per attention layer** (``lm.init_paged_cache``), all
  indexed by host-managed block tables (one
  :class:`~repro.serve.pagepool.PagePool` allocation covers the stack),
* **prefix-multicast prefill**: a prompt is first matched against the
  :class:`~repro.serve.prefix.PrefixCache`; matched pages are shared
  (refcount bump — no compute, no copy) and only the divergent suffix
  runs through the model, at its true positions, attending to the
  shared pages.  Cold prompts run the exact dense-path ``lm.prefill``
  and scatter into pages, so paged and dense serving produce identical
  token streams (CI-diffed),
* **chunked prefill** (``prefill_chunk=``): a long divergent suffix is
  split into fixed-size chunks, each run as its own ``decode_step``
  (the paged-attention supertile kernel on TPU) with its own pages
  charged as the block table grows — admission latency and the
  per-admission page spike are bounded by the chunk size, and chunk
  boundaries are provably invisible to the attention math (each chunk
  attends to the pages previous chunks wrote, exactly like decode),
* **bucketed compiles**: prompts/suffixes right-pad to shared length
  buckets — one XLA program per bucket instead of one per prompt
  length — with padded positions masked (dense) or redirected to the
  null page (paged),
* **decode page faults**: crossing a page boundary allocates on demand;
  a dry pool first evicts cold prefix chains, then **preempts** the
  youngest request by swapping its pages to host memory (bit-identical
  restore on re-admission),
* **copy-on-write**: a fork shares every page of its parent; the first
  divergent write to a shared page gets a private copy
  (``PagePool.cow`` + one device page copy).

Failure behavior (PR 6): the multicast design concentrates blast
radius — one bad chain or dry pool touches every request sharing the
prefix — so the engine degrades instead of crashing:

* admission that cannot proceed returns a **typed**
  :class:`~repro.serve.scheduler.Rejected` (``no-free-slot`` /
  ``watermark`` / ``pool-dry``) rather than silently stalling the queue
  head,
* a lost or corrupted preemption swap blob is detected before the
  scatter and the request is **re-prefilled from its own token stream**
  (prompt + generated tokens — greedy decode makes the replay
  token-identical) instead of restoring garbage,
* a mid-decode allocation or COW failure with nothing left to reclaim
  **requeues the slot** (bounded by ``MAX_DEGRADE_REQUEUES``, after
  which the request fails with a typed error) instead of raising,
* with ``kv_guard=True``, page chains are **fingerprinted** when they
  enter the prefix tree and verified at every sharing point: a
  corrupted chain is quarantined (dropped from the tree, readers
  requeued for replay) so it stops multicasting instead of poisoning
  every later consumer,
* with ``kernel_fallback=True``, a kernel dispatch that raises — or
  returns non-finite logits — is retried once on the reference backend
  of the same step (``kernels.call_with_fallback``) with a counted
  ``fallback`` stat.

All detectors are off-by-default flags; with both flags off and no
armed :class:`~repro.serve.faults.FaultPlan`, every code path is the
pre-existing one (CI diffs the token streams).

Mesh sharding (PR 8): with ``ServeConfig(num_shards=S)`` the pool is
partitioned into per-shard free lists (``pagepool.py``) and every
admission is routed to one shard — pinned via ``Request.shard`` or
balanced to the shard with the most free pages — where all its fresh
pages, COW copies, and watermark accounting live.  A prefix hit is
matched against that shard's **local** page copies; when the cached
chain continues on other shards, the engine allocates local pages and
**broadcasts** the chain's device bytes across the mesh (one
``_bcast_pages`` launch per chain — the paper's crossbar multicast at
pod scale), then registers the copies so every later consumer on the
shard hits locally.  ``broadcast_*`` counters account the payload and
the per-device fabric bytes under the configured ``mcast_mode``
(``dist.mcast.bytes_model(per_device=True)`` — the unicast / sw_tree /
hw hierarchy the HLO-level collectives in ``dist/mcast.py`` realise).
Passing ``mesh=`` (one device per shard along ``config.mesh_axis``)
shards the device page arrays along the page axis so that each shard's
page block is exactly one device's slice.  Each model step then runs as
one ``shard_map`` program per device (:func:`per_device`): the model
replicated, the paged attention on the device that owns each row's
pages (``nn.attention.write_and_attend``), one ``psum`` of the attention
output.  GSPMD moves the bytes of page copies and broadcasts.  Every
slot's pages live on its own shard (a cross-shard fork broadcasts the
parent's chain first).  Without a mesh the same sharded bookkeeping
runs on one device, which is what tier-1 tests.  ``num_shards=1`` is
the bitwise-identical PR 4-7 engine.
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec

from repro import kernels
from repro.dist import mcast
from repro.obs import trace
from repro.models import lm
from repro.nn import kvquant
from repro.nn.attention import PagedKvCache
from repro.serve import faults, guard, sampling
from repro.serve.config import ServeConfig, config_from_legacy
from repro.serve.pagepool import PagePool
from repro.serve.prefix import PrefixCache
from repro.serve.scheduler import Rejected, Scheduler

_PAGED = (PagedKvCache, kvquant.QuantPagedKvCache)

# a degraded slot (COW/alloc failure, lost swap, quarantine) re-enters
# the queue this many times before the request is failed with a typed
# error — the bound that turns a persistent fault into a clean rejection
# instead of an admission/preemption livelock
MAX_DEGRADE_REQUEUES = 8

# sentinel: _swap_in found the swap blob missing/corrupt (distinct from
# an admission Rejected — the caller degrades to a replay re-prefill)
_SWAP_LOST = object()


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    out: list[int] = dataclasses.field(default_factory=list)
    # pinned pool shard (host-side routing); None = balance to the shard
    # with the most free pages at admission
    shard: int | None = None
    # set when the engine permanently fails the request (typed reason);
    # failed requests are collected in PagedEngine.failed, never in run()'s
    # completed list
    error: str | None = None
    # preemption swap state:
    # (host page-data tree | None, n_pages, length, last_tok, checksum | None)
    _swap: tuple | None = dataclasses.field(default=None, repr=False)
    # degrade-requeue count (quarantine / lost swap / alloc+COW failure);
    # victim preemptions under memory pressure are normal and don't count
    _requeues: int = dataclasses.field(default=0, repr=False)


def bucket_len(n: int, bucket: int = 16) -> int:
    """Round a prompt/suffix length up to its shared compile bucket."""
    return max(bucket, math.ceil(n / bucket) * bucket)


def pad_to_bucket(tokens, bucket: int = 16) -> np.ndarray:
    """Right-pad a token list to its length bucket: (1, bucket_len)
    int32 — one XLA prefill program per bucket, not per prompt length."""
    out = np.zeros((1, bucket_len(len(tokens), bucket)), np.int32)
    out[0, : len(tokens)] = tokens
    return out


@dataclasses.dataclass
class _Slot:
    req: Request
    pages: list[int]  # page ids in block-table order (this slot's refs)
    length: int  # valid tokens (prompt + generated context so far)
    last_tok: int
    admit_seq: int
    shard: int = 0  # pool shard this slot allocates from


def _is_paged_leaf(x):
    return isinstance(x, _PAGED)


def _page_tree_map(fn, caches, *rest):
    return jax.tree.map(fn, caches, *rest, is_leaf=_is_paged_leaf)


def per_device(step, mesh, axis: str, n_args: int):
    """``step(params, caches, *rest) -> (logits, caches)`` as one program
    per device of a page pool sharded over ``mesh``'s ``axis``: params
    and inputs replicated, each device's caches its own page block
    (dim 2 of every leaf).  The model runs replicated (Mosaic kernels
    cannot be partitioned by GSPMD) and its paged attention, built with
    ``page_axis=axis``, works on the local block."""
    pool = PartitionSpec(None, None, axis)
    rep = PartitionSpec()
    return jax.shard_map(step, mesh=mesh,
                         in_specs=(rep, pool) + (rep,) * (n_args - 2),
                         out_specs=(rep, pool), check_vma=False)


class PagedEngine:
    """Continuous-batching server over the paged KV subsystem.

    Same ``run(requests)`` surface as the dense ``launch.serve.Server``
    fallback; requires an all-attention, global-window architecture
    (``lm.init_paged_cache`` enforces this)."""

    def __init__(self, cfg, params, *, config: ServeConfig | None = None,
                 mesh=None, draft=None, sampler: sampling.Sampler | None = None,
                 **legacy):
        if config is not None and legacy:
            raise TypeError(
                f"pass either config=ServeConfig(...) or legacy keywords, "
                f"not both: {sorted(legacy)}")
        if config is None:
            config = config_from_legacy(legacy)
        self.config = config
        self.cfg = cfg
        self.params = params
        self.max_batch = config.max_slots
        self.page_size = page_size = config.page_size
        self.table_width = config.cache_len // page_size
        self.cache_len = config.cache_len
        self.prompt_bucket = config.prompt_bucket
        # chunked prefill: divergent suffixes longer than this run as
        # fixed-size chunks (pages charged per chunk) instead of one
        # bucket-padded call — bounds the per-admission compute spike
        # without changing any token (chunk boundaries are invisible to
        # the attention math: each chunk attends to the pages the
        # previous chunks already wrote, exactly like decode does)
        self.prefill_chunk = config.prefill_chunk
        self.num_shards = config.num_shards
        self.mcast_mode = config.mcast_mode
        self.mesh = mesh
        self.mesh_axis = config.mesh_axis
        num_pages = config.num_pages
        if num_pages is None:
            # the dense fallback's footprint: one full-length cache per
            # batch slot, plus one null page per shard — rounded up so
            # every shard owns an equal page block AND can hold at least
            # one full-length request (admission routes a request to a
            # single shard)
            per_shard = max(
                -(-self.max_batch * self.table_width // self.num_shards),
                self.table_width)
            num_pages = self.num_shards * (per_shard + 1)
        self.pool = PagePool(num_pages, page_size, num_shards=self.num_shards)
        self.prefix = PrefixCache(self.pool, page_size)
        self.sched = Scheduler(self.pool, self.prefix,
                               watermark=config.watermark)
        # with a mesh, the device page arrays are sharded evenly over the
        # page axis, one device per shard: each shard's page block is
        # then exactly one device's slice
        if mesh is not None:
            n_dev = dict(mesh.shape)[self.mesh_axis]
            if n_dev != self.num_shards:
                raise ValueError(
                    f"mesh axis {self.mesh_axis!r} has {n_dev} devices but "
                    f"num_shards={self.num_shards}: the sharded pool needs "
                    f"one device per shard")
        self.caches = lm.init_paged_cache(
            cfg, num_pages, page_size, config.kv_dtype)
        if mesh is not None:
            def shard_leaf(a):
                spec = PartitionSpec(
                    *([None, None, self.mesh_axis] + [None] * (a.ndim - 3)))
                return jax.device_put(a, NamedSharding(mesh, spec))

            self.caches = _page_tree_map(
                lambda c: type(c)(*[shard_leaf(a) for a in c]), self.caches)
        self.slots: dict[int, _Slot] = {}
        self._admit_seq = 0
        self._requeue: list[Request] = []  # preempted, waiting to swap in
        self.n_preempted = 0
        self.n_cow = 0

        # page-chain broadcast accounting: payload = bytes of the pages
        # delivered (once), fabric = what each participant moves under
        # the configured multicast mode (the per-device bytes_model —
        # the unicast/sw_tree/hw hierarchy CI's bench row gates on)
        self.n_broadcast_chains = 0
        self.n_broadcast_pages = 0
        self.broadcast_payload_bytes = 0
        self.broadcast_fabric_bytes = 0.0
        total_bytes = sum(a.nbytes for a in jax.tree.leaves(self.caches))
        self.page_nbytes = total_bytes // num_pages
        self._fabric_mult = mcast.bytes_model(
            1, self.num_shards, per_device=True)[self.mcast_mode]
        self._fabric_mult_unicast = mcast.bytes_model(
            1, self.num_shards, per_device=True)["unicast"]
        self.kernel_calls: Counter[str] = Counter()  # per _dispatch name

        # sampling + speculative decoding (PR 10): the token choice is
        # one Sampler everywhere (admission, decode, verify-accept);
        # with spec_k > 0 a draft proposer runs ahead of the target and
        # `_step_spec` verifies k proposals in ONE chunked decode_step —
        # the supertile kernel's multicast KV fetch amortized across the
        # whole burst
        self.sampler = sampler if sampler is not None else \
            sampling.get_sampler(config.sampler)
        self.spec_k = config.spec_k
        self.spec = None
        if config.spec_k:
            from repro.serve import spec as spec_mod  # lazy: spec imports us
            self.spec = spec_mod.make_draft(
                config, cfg, draft=draft, max_slots=self.max_batch,
                cache_len=self.cache_len, sampler=self.sampler,
                kernel_calls=self.kernel_calls)
        self.n_spec_rounds = 0
        self.n_spec_drafted = 0
        self.n_spec_accepted = 0
        self.n_spec_rollbacks = 0
        self.n_spec_rollback_pages = 0

        # degradation state: detectors are opt-in flags; the counters
        # below surface in stats() so a degraded-but-alive server is
        # visible rather than silently slow
        self.kv_guard = config.kv_guard
        self.kernel_fallback = config.kernel_fallback
        self.fp = guard.PageFingerprints() if self.kv_guard else None
        self.failed: list[Request] = []  # permanently failed (typed error)
        self.rejections: Counter[str] = Counter()
        self.n_fallback = 0
        self.n_swap_dropped = 0
        self.n_quarantined_pages = 0
        self.n_degrade_requeues = 0

        # every jit that rewrites the page pools donates the cache
        # buffers: the engine always replaces self.caches with the
        # result, so XLA may update the (potentially large) pools in
        # place instead of copying them per call (a no-op on CPU).
        # With the kernel fallback armed, nothing is donated — a failed
        # primary call must leave its inputs intact for the reference
        # retry (part of the measured guard overhead).
        donate = () if self.kernel_fallback else (1,)

        # with a mesh, each model step is one program per device over
        # its own page block (see per_device)
        page_axis = self.mesh_axis if mesh is not None else None

        def decode(p, c, t, i, bt, ln):
            return lm.decode_step(p, cfg, c, t, i, block_table=bt, lengths=ln,
                                  page_axis=page_axis)

        def cold_prefill(p, caches, toks, li, table_row, length):
            logits, dense = lm.prefill(p, cfg, toks, logit_index=li)
            return logits, lm.prefill_to_pages(dense, caches, table_row, length,
                                               page_axis=page_axis)

        def suffix_prefill(p, caches, toks, li, table, index, length):
            logits, new_caches = lm.decode_step(
                p, cfg, caches, toks, index, block_table=table, lengths=length,
                page_axis=page_axis,
            )
            sel = jax.lax.dynamic_slice_in_dim(logits, li, 1, axis=1)
            return sel, new_caches

        if mesh is not None:
            decode = per_device(decode, mesh, page_axis, 6)
            cold_prefill = per_device(cold_prefill, mesh, page_axis, 6)
            suffix_prefill = per_device(suffix_prefill, mesh, page_axis, 7)
        self._builders = {
            "decode": decode,
            "cold_prefill": cold_prefill,
            "suffix_prefill": suffix_prefill,
            # verify is the decode math at s = spec_k + 1: one chunked
            # decode_step scoring every draft token at its true position
            # — its own dispatch name so kernel_calls / traces / the
            # analyzer separate verification from plain decode
            "verify": decode,
        }
        self._decode = jax.jit(decode, donate_argnums=donate)
        self._cold_prefill = jax.jit(cold_prefill, donate_argnums=donate)
        self._suffix_prefill = jax.jit(suffix_prefill, donate_argnums=donate)
        self._verify = jax.jit(decode, donate_argnums=donate)
        self._ref_jits: dict[str, object] = {}  # lazy reference-backend twins

        def copy_page(caches, src, dst):
            return _page_tree_map(
                lambda c: type(c)(
                    *[a.at[:, :, dst].set(a[:, :, src]) for a in c]
                ),
                caches,
            )

        self._copy_page = jax.jit(copy_page, donate_argnums=(0,))

        def bcast_pages(caches, src, dst):
            # one launch copies a whole page chain shard-to-shard: with a
            # mesh, src pages live on the owning shard's device and dst
            # on the consumer's, so GSPMD lowers this gather+scatter to
            # the actual cross-device transfer (mode-specific collective
            # schedules live in dist/mcast.py; the engine accounts their
            # fabric bytes via bytes_model).  src/dst are fixed-width,
            # null-page padded — the pad lanes self-copy page 0.
            return _page_tree_map(
                lambda c: type(c)(
                    *[a.at[:, :, dst].set(a[:, :, src]) for a in c]
                ),
                caches,
            )

        self._bcast_pages = jax.jit(bcast_pages, donate_argnums=(0,))
        self._gather_pages = jax.jit(
            lambda caches, ids: _page_tree_map(
                lambda c: type(c)(*[a[:, :, ids] for a in c]), caches
            )
        )
        self._scatter_pages = jax.jit(
            lambda caches, ids, data: _page_tree_map(
                lambda c, d: type(c)(
                    *[a.at[:, :, ids].set(b) for a, b in zip(c, d)]
                ),
                caches, data,
            ),
            donate_argnums=(0,),
        )

    # -- host bookkeeping ---------------------------------------------------
    def _free_slot(self) -> int | None:
        for s in range(self.max_batch):
            if s not in self.slots:
                return s
        return None

    def _table_row(self, pages: list[int]) -> np.ndarray:
        row = np.zeros(self.table_width, np.int32)
        row[: len(pages)] = pages
        return row

    def _pages_ids_fixed(self, pages: list[int]) -> jnp.ndarray:
        """Fixed-width page-id vector (padded with the null page) so the
        swap gather/scatter jits compile once, not once per page count."""
        return jnp.asarray(self._table_row(pages))

    def _pick_shard(self, req: Request) -> int:
        """The pool shard an admission allocates from: the request's
        pinned shard when set (host-side routing), else the shard with
        the most free pages, ties to the lowest index.  Decided from
        committed pool state only, so the async loop and the sync oracle
        route identically for the same admission order."""
        if req.shard is not None:
            if not 0 <= req.shard < self.num_shards:
                raise ValueError(
                    f"request {req.rid}: pinned shard {req.shard} out of "
                    f"range (num_shards={self.num_shards})")
            return req.shard
        return max(range(self.num_shards),
                   key=lambda s: (self.pool.free_pages_on(s), -s))

    def _broadcast_chain(self, src: list[int], dst: list[int]) -> None:
        """Deliver the device bytes of cached pages ``src`` (copies on
        other shards) into freshly allocated local pages ``dst`` — the
        page-chain multicast crossing the mesh — and account the
        traffic under the configured ``mcast_mode``."""
        pad = np.zeros(self.table_width, np.int32)
        s, d = pad.copy(), pad.copy()
        s[: len(src)] = src
        d[: len(dst)] = dst
        self.caches = self._bcast_pages(
            self.caches, jnp.asarray(s), jnp.asarray(d))
        self.n_broadcast_chains += 1
        self.n_broadcast_pages += len(dst)
        payload = len(dst) * self.page_nbytes
        self.broadcast_payload_bytes += payload
        self.broadcast_fabric_bytes += payload * self._fabric_mult
        rec = trace.active()
        if rec is not None:
            rec.instant("mcast.broadcast", cat="engine", args={
                "pages": len(dst), "payload_bytes": payload,
                "fabric_bytes": payload * self._fabric_mult,
                "unicast_bytes": payload * self._fabric_mult_unicast,
                "mode": self.mcast_mode,
            })

    # -- guarded kernel dispatch --------------------------------------------
    def _ref_variant(self, name):
        """Reference-backend twin of a jitted model step, traced lazily
        under a forced ``reference`` policy (same math as the pre-kernel
        call sites) and never donating — the retry target of
        ``kernels.call_with_fallback``."""
        fn = self._ref_jits.get(name)
        if fn is None:
            jfn = jax.jit(self._builders[name])

            def fn(*args, _jfn=jfn):
                with kernels.use_policy("reference"):
                    return jfn(*args)

            self._ref_jits[name] = fn
        return fn

    def _dispatch(self, name, *args):
        """Run one jitted model step (``decode`` / ``cold_prefill`` /
        ``suffix_prefill``) through the fault-injection sites and — when
        ``kernel_fallback`` is armed — the retry-once-on-reference path
        with the opt-in non-finite-logits check."""
        primary_fn = getattr(self, f"_{name}")

        def primary(*a):
            if faults.fires("kernel.raise") is not None:
                raise faults.InjectedFault(f"injected kernel fault in {name}")
            out = primary_fn(*a)
            if faults.fires("kernel.nan") is not None:
                out = (jnp.full_like(out[0], jnp.nan), out[1])
            return out

        self.kernel_calls[name] += 1
        rec = trace.active()
        t0 = rec.now() if rec is not None else 0.0
        if not self.kernel_fallback:
            out = primary(*args)
            fell_back = False
        else:
            out, fell_back = kernels.call_with_fallback(
                primary, self._ref_variant(name), *args,
                check=lambda o: kernels.all_finite(o[0]),
            )
            if fell_back:
                self.n_fallback += 1
        if rec is not None:
            rec.complete(f"engine.{name}", t0, cat="kernel",
                         args={"fallback": fell_back})
        return out

    # -- admission ----------------------------------------------------------
    def _reject(self, rej: Rejected) -> Rejected:
        self.rejections[rej.reason] += 1
        return rej

    def _admit(self, req: Request) -> bool | Rejected:
        """Admit a queued request: ``True`` on success, a falsy typed
        :class:`Rejected` otherwise (existing ``while queue and
        self._admit(...)`` loops keep working; callers that care read
        the reason)."""
        rec = trace.active()
        if rec is None:
            return self._admit_impl(req)
        t0 = rec.now()
        res = self._admit_impl(req)
        rec.complete("engine.admit", t0, cat="engine",
                     args={"rid": req.rid, "ok": res is True})
        return res

    def _admit_impl(self, req: Request) -> bool | Rejected:
        slot = self._free_slot()
        if slot is None:
            return self._reject(Rejected("no-free-slot"))
        if req._swap is not None:
            res = self._swap_in(slot, req)
            if res is not _SWAP_LOST:
                return res
            # the swap blob was dropped or failed its checksum: the KV
            # bytes are gone, but the token stream is not — fall through
            # and re-prefill from prompt + generated tokens (greedy
            # decode makes the replay token-identical)
            self.n_swap_dropped += 1
            req._swap = None
            rec = trace.active()
            if rec is not None:
                rec.instant("engine.swap_lost", cat="engine",
                            args={"rid": req.rid})
        replay = bool(req.out)
        tokens = req.prompt + req.out[:-1] if replay else req.prompt
        if len(req.prompt) + req.max_new + 1 > self.cache_len:
            raise ValueError(
                f"request {req.rid}: prompt+max_new exceeds cache_len "
                f"{self.cache_len}"
            )
        ref0 = list(self.pool._ref) if self.kv_guard else None
        shard = self._pick_shard(req)
        # match BEFORE the watermark check: the refs it takes pin the
        # chain against can_admit's prefix eviction; a rejected
        # admission fully unwinds it (refs and stats).  Only this
        # shard's local copies match; the chain's continuation on other
        # shards is a broadcast candidate (refs taken only on commit)
        shared, n_matched = self.prefix.match(tokens, shard)
        remote = self.prefix.remote_continuation(tokens, shard, len(shared))
        if self.kv_guard and (shared or remote):
            bad = self.fp.verify(
                self.caches, shared + [pid for _, pid in remote])
            if bad:
                # corruption caught at the sharing point: quarantine the
                # chain (and its poisoned readers) instead of letting it
                # multicast — or broadcast cross-shard — to this and
                # every later consumer
                self.prefix.unmatch(shared, len(tokens))
                self._quarantine(bad)
                shared, n_matched, remote = [], 0, []
                ref0 = list(self.pool._ref) if self.kv_guard else None
        # broadcast pages count as fresh demand: they are allocated on
        # this shard like any other fresh page — only their *bytes* come
        # over the fabric instead of through a re-prefill
        fresh_needed = self.sched.pages_for(len(tokens) + 1) - len(shared)
        rej = self.sched.check_admission(fresh_needed, shard)
        if rej is not None:
            self.prefix.unmatch(shared, len(tokens))
            self._assert_refs_unchanged(ref0, "rejected admission")
            return self._reject(rej)
        if remote:
            # the multicast at pod scale: the owning shard prefilled the
            # chain once; every other shard receives the bytes via one
            # collective instead of re-running the model over the prefix
            got = self.pool.alloc(len(remote), shard)
            if got is None:  # injected exhaustion after a green check
                self.prefix.unmatch(shared, len(tokens))
                self._assert_refs_unchanged(ref0, "rejected admission")
                return self._reject(Rejected("pool-dry", len(remote)))
            self._broadcast_chain([pid for _, pid in remote], got)
            self.prefix.commit_broadcast([n for n, _ in remote], shard, got)
            if self.kv_guard:
                self.fp.record(self.caches, got)
            shared = shared + got
            n_matched += len(got) * self.page_size
            # the commit is durable even if the admission later unwinds
            # (the tree keeps the copies) — re-baseline the refcount net
            ref0 = list(self.pool._ref) if self.kv_guard else None

        if n_matched == 0:
            # cold prompt: the dense path's own prefill, scattered into
            # pages — bit-identical bytes to the dense fallback
            fresh = self.pool.alloc(fresh_needed, shard)
            if fresh is None:  # injected exhaustion after a green check
                self._assert_refs_unchanged(ref0, "rejected admission")
                return self._reject(Rejected("pool-dry", fresh_needed))
            pages = fresh
            toks = pad_to_bucket(tokens, self.prompt_bucket)
            logits, self.caches = self._dispatch(
                "cold_prefill",
                self.params, self.caches, jnp.asarray(toks),
                jnp.int32(len(tokens) - 1),
                jnp.asarray(self._table_row(pages)), jnp.int32(len(tokens)),
            )
        else:
            # prefix hit: the shared pages are "multicast" to this
            # request (refcount bump, zero compute) — only the divergent
            # suffix runs, attending to the shared pages at its true
            # positions, split into fixed-size chunks when it outgrows
            # ``prefill_chunk`` (each chunk is charged its own pages —
            # can_admit reserved the full demand, so the draws succeed
            # unless a fault plan forces exhaustion mid-suffix, which
            # unwinds the whole admission)
            pages = list(shared)
            suffix = tokens[n_matched:]
            chunk = self.prefill_chunk or len(suffix)
            for c0 in range(0, len(suffix), chunk):
                ctoks = suffix[c0 : c0 + chunk]
                last_chunk = c0 + chunk >= len(suffix)
                # the final chunk also covers the first decode write
                end = len(tokens) + 1 if last_chunk else n_matched + c0 + len(ctoks)
                need = self.sched.pages_for_range(
                    len(pages) * self.page_size, end
                )
                if need:
                    got = self.pool.alloc(need, shard)
                    if got is None:  # injected mid-suffix exhaustion
                        fresh_far = [p for p in pages if p not in shared]
                        if fresh_far:
                            self.pool.release(fresh_far)
                        self.prefix.unmatch(shared, len(tokens))
                        self._assert_refs_unchanged(ref0, "rejected admission")
                        return self._reject(Rejected("pool-dry", need))
                    pages.extend(got)
                toks = pad_to_bucket(ctoks, self.prompt_bucket)
                logits, self.caches = self._dispatch(
                    "suffix_prefill",
                    self.params, self.caches, jnp.asarray(toks),
                    jnp.int32(len(ctoks) - 1),
                    jnp.asarray(self._table_row(pages))[None],
                    jnp.asarray([n_matched + c0], jnp.int32),
                    jnp.asarray([n_matched + c0 + len(ctoks)], jnp.int32),
                )
        self.prefix.insert(tokens, pages, shard)
        n_tree = len(tokens) // self.page_size
        if self.kv_guard and n_tree:
            self.fp.record(self.caches, pages[:n_tree])
        f = faults.fires("page.corrupt")
        if f is not None and n_tree:
            # flip bytes in one page of the chain this admission cached:
            # the corruption a later prefix hit must detect
            self._corrupt_page(pages[min(f.page_index, n_tree - 1)])
        self.slots[slot] = _Slot(
            req=req, pages=pages, length=len(tokens),
            last_tok=(req.out[-1] if replay
                      else int(self.sampler.select(logits)[0, -1])),
            admit_seq=self._admit_seq, shard=shard,
        )
        self._admit_seq += 1
        if not replay:
            req.out.append(self.slots[slot].last_tok)
        return True

    def _assert_refs_unchanged(self, ref0, what: str) -> None:
        """kv_guard regression net: a ``what`` path must leave every
        refcount exactly as found."""
        if ref0 is not None and ref0 != self.pool._ref:
            delta = {
                pid: (a, b)
                for pid, (a, b) in enumerate(zip(ref0, self.pool._ref))
                if a != b
            }
            raise guard.GuardViolation(
                f"{what} changed page refcounts: {delta} (page: (before, after))"
            )

    def _corrupt_page(self, pid: int) -> None:
        """Injected corruption (``page.corrupt``): perturb one element of
        every array of page ``pid`` — the single-bit-flip stand-in the
        fingerprint verify must catch."""
        def flip(c):
            return type(c)(*[
                a.at[(slice(None), slice(None), pid) + (0,) * (a.ndim - 3)]
                .add(jnp.asarray(1, a.dtype).astype(a.dtype))
                for a in c
            ])

        self.caches = _page_tree_map(flip, self.caches)

    def _quarantine(self, bad_pages: list[int]) -> None:
        """Drop the corrupted chain from the prefix tree and requeue any
        running slot still reading one of its pages (their replay
        re-prefills from tokens — correct bytes — so only the chain is
        lost, not its consumers)."""
        dropped = self.prefix.drop(bad_pages)
        self.fp.forget(dropped)
        self.n_quarantined_pages += len(dropped)
        rec = trace.active()
        if rec is not None:
            rec.instant("engine.quarantine", cat="engine",
                        args={"pages": len(dropped)})
        poisoned = set(bad_pages)
        for slot, st in list(self.slots.items()):
            if poisoned & set(st.pages):
                self._requeue_degraded(slot, "quarantined page in block table")

    def _requeue_degraded(self, slot: int, why: str) -> None:
        """Degradation path shared by quarantine and alloc/COW failure:
        free the slot's pages and send the request back to the queue as
        a replay (no swap blob — it re-prefills from its own tokens).
        Bounded: past ``MAX_DEGRADE_REQUEUES`` the request fails with a
        typed error instead of ping-ponging forever."""
        st = self.slots.pop(slot)
        self.pool.release(st.pages)
        st.req._swap = None
        st.req._requeues += 1
        if st.req._requeues > MAX_DEGRADE_REQUEUES:
            st.req.error = f"degraded too often ({why})"
            self.failed.append(st.req)
            return
        self.n_degrade_requeues += 1
        self._requeue.append(st.req)

    # -- preemption (swap to host) and resume -------------------------------
    def _preempt(self, slot: int) -> None:
        st = self.slots.pop(slot)
        ids = self._pages_ids_fixed(st.pages)
        data = jax.device_get(self._gather_pages(self.caches, ids))
        if faults.fires("swap.drop") is not None:
            data = None  # injected loss of the host swap blob
        checksum = (
            guard.blob_checksum(data)
            if self.kv_guard and data is not None else None
        )
        st.req._swap = (data, len(st.pages), st.length, st.last_tok, checksum)
        rec = trace.active()
        if rec is not None:
            rec.instant("engine.preempt", cat="engine",
                        args={"rid": st.req.rid, "pages": len(st.pages),
                              "shard": st.shard})
        self.pool.release(st.pages)
        self._requeue.append(st.req)
        self.n_preempted += 1

    def _swap_in(self, slot: int, req: Request):
        """Restore a preempted request: ``True``, a typed ``Rejected``,
        or the ``_SWAP_LOST`` sentinel when the blob is missing/corrupt
        (the caller degrades to a replay re-prefill)."""
        data, n_pages, length, last_tok, checksum = req._swap
        if data is None:
            return _SWAP_LOST
        if checksum is not None and guard.blob_checksum(data) != checksum:
            return _SWAP_LOST
        shard = self._pick_shard(req)  # swap-in re-routes like any admission
        rej = self.sched.check_admission(n_pages, shard)
        if rej is not None:
            return self._reject(rej)
        pages = self.pool.alloc(n_pages, shard)
        if pages is None:  # injected exhaustion after a green check
            return self._reject(Rejected("pool-dry", n_pages))
        ids = self._pages_ids_fixed(pages)
        self.caches = self._scatter_pages(self.caches, ids, data)
        req._swap = None
        rec = trace.active()
        if rec is not None:
            rec.instant("engine.swap_in", cat="engine",
                        args={"rid": req.rid, "pages": n_pages,
                              "shard": shard})
        self.slots[slot] = _Slot(
            req=req, pages=pages, length=length, last_tok=last_tok,
            admit_seq=self._admit_seq, shard=shard,
        )
        self._admit_seq += 1
        return True

    def _pick_victim(self, exclude: set[int] = frozenset(),
                     shard: int | None = None) -> int | None:
        """Youngest running slot outside ``exclude`` — restricted to
        ``shard``'s slots when given: preempting a slot on another shard
        frees pages the starved allocation cannot use."""
        order = sorted(
            (s for s in self.slots
             if s not in exclude
             and (shard is None or self.slots[s].shard == shard)),
            key=lambda s: self.slots[s].admit_seq,
        )
        return self.sched.pick_victim(order)

    # -- copy-on-write / fork ----------------------------------------------
    def fork(self, slot: int, req: Request,
             shard: int | None = None) -> int | None:
        """Fork a running request.  On the parent's shard the child
        shares every page of the parent (one refcount bump per page — no
        copies) and the next write to the shared tail page
        copy-on-writes.  Returns the child slot, or None when no slot
        (or, cross-shard, no page) is free.

        ``shard`` places the child on another shard (default: the
        parent's, or the request's pinned one).  A slot's pages all live
        on its own shard, so a cross-shard fork broadcasts the parent's
        chain into fresh pages there instead of sharing it."""
        child_slot = self._free_slot()
        if child_slot is None:
            return None
        st = self.slots[slot]
        if shard is None:
            shard = st.shard if req.shard is None else req.shard
        if shard == st.shard:
            self.pool.share(st.pages)
            pages = list(st.pages)
        else:
            pages = self.pool.alloc(len(st.pages), shard)
            if pages is None:
                return None
            self._broadcast_chain(st.pages, pages)
        self.slots[child_slot] = _Slot(
            req=req, pages=pages, length=st.length,
            last_tok=st.last_tok, admit_seq=self._admit_seq, shard=shard,
        )
        self._admit_seq += 1
        req.out.extend(st.req.out)
        return child_slot

    def _alloc_for_decode(self, n: int, *, exclude: set[int],
                          shard: int = 0) -> list[int] | None:
        """Allocate decode pages on ``shard``, escalating: free list ->
        prefix eviction -> preemption of the youngest same-shard request
        not in ``exclude`` (a slot never preempts itself via a *victim*
        pick — progress; a slot on another shard is never preempted —
        its pages could not satisfy this shard's demand)."""
        while True:
            if self.sched.reclaim(n, shard):
                got = self.pool.alloc(n, shard)
                if got is not None:
                    return got
                # an armed fault plan can fail the alloc even after a
                # green reclaim — fall through to the escalation below
            victim = self._pick_victim(exclude, shard)
            if victim is None:
                return None
            self._preempt(victim)

    def _ensure_writable(self, slot: int, n: int = 1) -> bool:
        """Before a decode step writes positions ``length .. length+n-1``
        (``n > 1`` for a speculative verify burst): make sure every
        covering page exists in the slot's table and is exclusively
        owned (COW).  Returns False when the slot could not be made
        writable and was requeued instead (degradation — the step
        proceeds without it).  ``n=1`` is the pre-PR 10 single-write
        path, page for page."""
        st = self.slots[slot]
        last = (st.length + n - 1) // self.page_size
        if last >= self.table_width:
            raise RuntimeError(f"request {st.req.rid} overran cache_len")
        for need in range(st.length // self.page_size, last + 1):
            if need >= len(st.pages):
                got = self._alloc_for_decode(1, exclude={slot}, shard=st.shard)
                if got is None:
                    self._requeue_degraded(
                        slot, "page fault with pool exhausted")
                    return False
                st.pages.extend(got)
            elif self.pool.refcount(st.pages[need]) > 1:
                # the private copy lands on the page's shard, which is
                # the slot's own (fork broadcasts across shards)
                res = self.pool.cow(st.pages[need])
                if res is None:  # pool dry: make room, then retry the COW
                    got = self._alloc_for_decode(
                        1, exclude={slot}, shard=st.shard)
                    if got is not None:
                        self.pool.release(got)
                        res = self.pool.cow(st.pages[need])
                if res is None:
                    self._requeue_degraded(
                        slot, "COW failure with pool exhausted")
                    return False
                new_id, copied = res
                if copied:
                    self.caches = self._copy_page(
                        self.caches, jnp.int32(st.pages[need]),
                        jnp.int32(new_id)
                    )
                    self.n_cow += 1
                st.pages[need] = new_id
        return True

    # -- main loop ----------------------------------------------------------
    def step(self) -> list[Request]:
        """One decode step over the active batch; returns finished requests."""
        rec = trace.active()
        if rec is None:
            return self._step_impl()
        t0 = rec.now()
        n_slots = len(self.slots)
        out = self._step_impl()
        rec.complete("engine.step", t0, cat="engine",
                     args={"n_slots": n_slots, "finished": len(out)})
        return out

    def _step_impl(self) -> list[Request]:
        if self.spec is not None and self.slots:
            # per-round draft width: k proposals need k+1 scored
            # positions, and the LAST committed token of a request must
            # come from a step whose width its budget allows — clamp k
            # so no slot can overshoot max_new, and fall through to the
            # plain path when even k=1 doesn't fit (this keeps the
            # near-finish tail token-identical to non-speculative runs)
            k = min(self.spec_k,
                    min(st.req.max_new - len(st.req.out)
                        for st in self.slots.values()) - 1)
            if k >= 1:
                return self._step_spec(k)
        for slot in sorted(self.slots, key=lambda s: self.slots[s].admit_seq):
            if slot in self.slots:  # a page fault may preempt later slots
                self._ensure_writable(slot)
        if not self.slots:
            return []
        toks = np.zeros((self.max_batch, 1), np.int32)
        index = np.zeros(self.max_batch, np.int32)
        lengths = np.zeros(self.max_batch, np.int32)
        table = np.zeros((self.max_batch, self.table_width), np.int32)
        for slot, st in self.slots.items():
            toks[slot, 0] = st.last_tok
            index[slot] = st.length
            lengths[slot] = st.length + 1
            table[slot] = self._table_row(st.pages)
        logits, self.caches = self._dispatch(
            "decode",
            self.params, self.caches, jnp.asarray(toks), jnp.asarray(index),
            jnp.asarray(table), jnp.asarray(lengths),
        )
        nxt = self.sampler.select(logits)[:, -1]
        finished = []
        for slot, st in list(self.slots.items()):
            st.length += 1
            st.last_tok = int(nxt[slot])
            st.req.out.append(st.last_tok)
            if len(st.req.out) >= st.req.max_new:
                finished.append(st.req)
                self.pool.release(st.pages)
                del self.slots[slot]
        return finished

    def _step_spec(self, k: int) -> list[Request]:
        """One speculative verify-accept round: the draft proposes ``k``
        tokens per slot, the target scores all of them (plus the pending
        token) in ONE chunked ``decode_step`` — the supertile kernel's
        single multicast KV fetch per chunk, now on the decode hot path
        — and each slot commits the longest accepted prefix.

        Indexing: the verify call feeds ``[last_tok, d_1..d_k]`` at
        ``index = length``; scored position ``i`` predicts the token
        *after* draft ``i``, so the sampler's choice at position ``i``
        is the ground truth draft ``i+1`` is checked against.  A round
        commits ``c = min(a+1, k)`` target tokens (``a`` = accepted
        drafts): the ``a+1``-th is the free token every verify step
        yields; capping at ``k`` keeps the draft cache exactly one
        pending token behind (uniform lag — no catch-up widths).

        Rollback: rejected drafts wrote real K/V into real pages, but
        ``lengths`` masks them and any page past the committed length is
        released here — every such page was made exclusively owned by
        ``_ensure_writable`` (fresh or COW), so the release keeps pool
        refcounts, prefix chains, and ``check()`` audits exactly green.
        """
        from repro.serve.spec import SlotView  # lazy: spec imports engine
        for slot in sorted(self.slots, key=lambda s: self.slots[s].admit_seq):
            if slot in self.slots:  # a page fault may preempt later slots
                self._ensure_writable(slot, k + 1)
        if not self.slots:
            return []
        views = {
            slot: SlotView(rid=st.req.rid,
                           tokens=tuple(st.req.prompt) + tuple(st.req.out),
                           length=st.length)
            for slot, st in self.slots.items()
        }
        drafts = np.asarray(self.spec.propose(views, k), np.int32)
        toks = np.zeros((self.max_batch, k + 1), np.int32)
        index = np.zeros(self.max_batch, np.int32)
        lengths = np.zeros(self.max_batch, np.int32)
        table = np.zeros((self.max_batch, self.table_width), np.int32)
        for slot, st in self.slots.items():
            toks[slot, 0] = st.last_tok
            toks[slot, 1:] = drafts[slot]
            index[slot] = st.length
            lengths[slot] = st.length + k + 1
            table[slot] = self._table_row(st.pages)
        logits, self.caches = self._dispatch(
            "verify",
            self.params, self.caches, jnp.asarray(toks), jnp.asarray(index),
            jnp.asarray(table), jnp.asarray(lengths),
        )
        target = self.sampler.select(logits)        # (max_batch, k+1)
        accepted = self.sampler.verify(drafts, target)
        finished = []
        new_lengths: dict[int, int] = {}
        n_accepted = n_committed = n_rollback_pages = 0
        for slot, st in list(self.slots.items()):
            a = int(accepted[slot])
            c = min(a + 1, k, st.req.max_new - len(st.req.out))
            st.req.out.extend(int(t) for t in target[slot, :c])
            st.length += c
            st.last_tok = int(target[slot, c - 1])
            self.n_spec_drafted += k
            self.n_spec_accepted += a
            n_accepted += a
            n_committed += c
            # trim the pages only the rejected tail reached — all of
            # them exclusively owned (see docstring), so releasing them
            # restores the exact page invariant of a plain decode step
            keep = (st.length - 1) // self.page_size + 1
            if keep < len(st.pages):
                self.pool.release(st.pages[keep:])
                n_rollback_pages += len(st.pages) - keep
                self.n_spec_rollback_pages += len(st.pages) - keep
                del st.pages[keep:]
            if a < k:
                self.n_spec_rollbacks += 1
            if len(st.req.out) >= st.req.max_new:
                finished.append(st.req)
                self.pool.release(st.pages)
                del self.slots[slot]
                self.spec.forget(slot)
            else:
                new_lengths[slot] = st.length
        self.spec.observe(new_lengths)
        self.n_spec_rounds += 1
        rec = trace.active()
        if rec is not None:
            rec.instant("spec.verify", cat="engine", args={
                "k": k, "n_slots": len(views),
                "drafted": k * len(views), "accepted": n_accepted,
                "committed": n_committed,
                "rollback_pages": n_rollback_pages,
            })
        return finished

    def run(self, requests: list[Request]) -> list[Request]:
        queue = list(requests)
        done: list[Request] = []
        stall = 0  # consecutive empty-batch rounds with a rejected head
        while queue or self.slots or self._requeue:
            if self._requeue:  # preempted requests re-enter at the front
                queue = self._requeue + queue
                self._requeue = []
            last_rej: Rejected | bool = True
            while queue:
                last_rej = self._admit(queue[0])
                if not last_rej:
                    break
                queue.pop(0)
            if self.slots:
                stall = 0
                done.extend(self.step())
                continue
            if not queue:
                continue  # degraded requeues merge next round
            # nothing running and the head was rejected: without faults
            # this is deterministic — raise immediately; with a plan
            # armed the rejection may be transient, so retry a bounded
            # number of rounds before declaring the pool undersized
            stall += 1
            if faults.active() is None or stall > 100:
                raise RuntimeError(
                    f"pool too small to admit any queued request "
                    f"(head rejected: {last_rej!r})"
                )
        return done

    # -- auditing ------------------------------------------------------------
    def check(self) -> None:
        """Run the pool auditor with the engine's live holders: every
        running slot's chain plus the prefix tree's own references.
        Raises :class:`repro.serve.guard.GuardViolation` on a leaked or
        dropped reference; green after every step/run by construction."""
        holders = [st.pages for st in self.slots.values()]
        holders.append(self.prefix.pages())
        self.pool.check(holders)

    # -- introspection ------------------------------------------------------
    def stats(self) -> dict:
        out = {
            "pool": dataclasses.asdict(self.pool.stats),
            "free_pages": self.pool.free_pages,
            "prefix_pages": len(self.prefix),
            "prefix_hit_tokens": self.prefix.hit_tokens,
            "prefix_miss_tokens": self.prefix.miss_tokens,
            "preempted": self.n_preempted,
            "cow_copies": self.n_cow,
            "rejected": dict(self.rejections),
            "kernel_fallbacks": self.n_fallback,
            "swap_dropped": self.n_swap_dropped,
            "quarantined_pages": self.n_quarantined_pages,
            "degrade_requeues": self.n_degrade_requeues,
            "failed": len(self.failed),
            "num_shards": self.num_shards,
            "broadcast_chains": self.n_broadcast_chains,
            "broadcast_pages": self.n_broadcast_pages,
            "broadcast_payload_bytes": self.broadcast_payload_bytes,
            "broadcast_fabric_bytes": self.broadcast_fabric_bytes,
            "spec_rounds": self.n_spec_rounds,
            "spec_drafted": self.n_spec_drafted,
            "spec_accepted": self.n_spec_accepted,
            "spec_rollbacks": self.n_spec_rollbacks,
            "spec_rollback_pages": self.n_spec_rollback_pages,
            "accept_rate": self.n_spec_accepted / max(1, self.n_spec_drafted),
        }
        for s in range(self.num_shards):
            out[f"shard{s}_free_pages"] = self.pool.free_pages_on(s)
            out[f"shard{s}_in_use"] = (
                self.pool.pages_per_shard - self.pool.free_pages_on(s))
        return out

    # stats() keys that are point-in-time gauges, not cumulative counters:
    # stats_delta reports their current value rather than a difference
    _STAT_GAUGES = frozenset(
        {"free_pages", "prefix_pages", "peak_in_use", "num_shards",
         "accept_rate"})
    # every per-shard stat is a point-in-time occupancy gauge; matching
    # the whole family (rather than one hand-listed suffix) keeps new
    # shard{s}_* keys from silently passing through as counter deltas
    _SHARD_GAUGE_RE = re.compile(r"shard\d+_")

    def _is_gauge(self, key: str) -> bool:
        k = key.removeprefix("pool_")
        return (k in self._STAT_GAUGES
                or self._SHARD_GAUGE_RE.match(k) is not None)

    def flat_stats(self) -> dict:
        """:meth:`stats` with the nesting removed: ``pool`` counters as
        ``pool_*`` keys, per-reason rejections as ``rejected_<reason>``
        — the shape :mod:`repro.serve.metrics` merges into its flat
        snapshot."""
        flat: dict = {}
        for key, val in self.stats().items():
            if key == "pool":
                flat.update({f"pool_{k}": v for k, v in val.items()})
            elif key == "rejected":
                flat.update({f"rejected_{k}": v for k, v in val.items()})
            else:
                flat[key] = val
        return flat

    def stats_delta(self) -> dict:
        """Flat dict of counter *deltas* since the previous
        ``stats_delta`` call (first call: since engine construction), so
        per-window consumers — the metrics snapshot, a bench row's
        per-trace accounting — never re-diff nested cumulative stats by
        hand.  Gauges (``free_pages``, ``prefix_pages``,
        ``pool_peak_in_use``, ``num_shards``, and the whole per-shard
        ``shard{s}_*`` occupancy family) report their current value."""
        flat = self.flat_stats()
        prev = getattr(self, "_stats_prev", {})
        self._stats_prev = flat
        return {
            k: v if self._is_gauge(k) else v - prev.get(k, 0)
            for k, v in flat.items()
        }
