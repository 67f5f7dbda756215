"""Mesh-sharded page pool + multicast page-chain broadcast (PR 8).

Covers the sharded serving stack on a single device (the forced-multi-
device mesh legs live in test_dist_serve.py):

* PagePool shard partition: one null-led page block per shard, the
  (shard, local_page) mapping, per-shard free lists, containment audit,
  single-shard degenerate grant order;
* ServeConfig: validation, argparse derivation, the one-warning legacy
  keyword shim, bitwise S=1 parity between config and legacy call sites;
* 4-shard engine == dense oracle (cold and shared-prefix), with the
  prefix chain allocated once per owning shard and *broadcast* — not
  re-prefilled — to the other shards (counter asserts);
* cross-shard fork/COW, per-shard watermark + shard-local preemption,
  per-shard prefix eviction, per-device bytes_model hierarchy, and the
  broadcast-aware metrics snapshot schema.
"""
import argparse
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist import mcast
from repro.launch.serve import Server
from repro.models import lm
from repro.serve import (
    MCAST_MODES,
    PagedEngine,
    PagePool,
    PrefixCache,
    Rejected,
    Request,
    Scheduler,
    ServeConfig,
    ServeMetrics,
    add_serve_args,
    validate_snapshot,
)
from repro.serve import config as serve_config

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small():
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = lm.init(cfg, KEY)
    return cfg, params


def _mk_requests(cfg, *, shared_prefix=0, n=4, max_new=5, seed=7, shards=None):
    rng = np.random.default_rng(seed)
    prefix = list(rng.integers(0, cfg.vocab, size=shared_prefix))
    return [
        Request(rid=i,
                prompt=prefix + list(rng.integers(0, cfg.vocab, size=3 + i)),
                max_new=max_new,
                shard=None if shards is None else shards[i])
        for i in range(n)
    ]


def _clone(reqs):
    return [Request(rid=r.rid, prompt=list(r.prompt), max_new=r.max_new,
                    shard=r.shard)
            for r in reqs]


# ---------------------------------------------------------------------------
# page pool sharding
# ---------------------------------------------------------------------------


def test_pool_shard_partition_and_mapping():
    p = PagePool(36, 8, num_shards=4)
    assert p.pages_per_shard == 8 and p.block == 9
    assert p.free_pages == 32
    # shard s owns the block [9s, 9s + 9): its own null page 9s, then
    # the allocatable ids [9s + 1, 9s + 9)
    for s in range(4):
        assert p.free_pages_on(s) == 8
        ids = p.alloc(2, s)
        assert ids == [1 + s * 9, 2 + s * 9]
        assert [p.shard_of(i) for i in ids] == [s, s]
        assert [p.local_page(i) for i in ids] == [1, 2]
        p.release(ids)
    # a freed page returns to its OWNING shard's free list
    ids = p.alloc(3, 1)
    p.release(ids)
    assert p.free_pages_on(1) == 8
    p.check()
    # the null pages belong to no shard and are never granted or freed
    for null in (0, 9, 18, 27):
        assert p.is_null(null)
        with pytest.raises(ValueError):
            p.shard_of(null)
        with pytest.raises(ValueError):
            p.local_page(null)
        with pytest.raises(ValueError):
            p.release([null])


@pytest.mark.parametrize("num_shards", [1, 4])
def test_pool_shard_ids_fall_in_one_device_block(num_shards):
    """Each shard's ids are exactly one device's slice of a page axis of
    ``num_pages`` split evenly over ``num_shards`` devices; with one
    shard the ids are the PR 4-7 pool's ``[1, num_pages)``."""
    pps = 8
    p = PagePool(num_shards * (pps + 1), 8, num_shards=num_shards)
    blocks = np.array_split(np.arange(p.num_pages), num_shards)
    for s, block in enumerate(blocks):
        ids = p.alloc(pps, s)
        assert set(ids) <= set(block[1:].tolist())  # block[0]: the null
        assert p.alloc(1, s) is None  # the shard is full
        assert [p.local_page(i) for i in ids] == list(range(1, pps + 1))
    if num_shards == 1:
        assert ids == list(range(1, p.num_pages))


def test_pool_per_shard_exhaustion_is_contained():
    p = PagePool(10, 8, num_shards=2)  # 4 pages per shard
    got = p.alloc(4, 0)
    assert got is not None and p.free_pages_on(0) == 0
    # shard 0 dry: an all-or-nothing grant there fails...
    assert p.alloc(1, 0) is None
    # ...while shard 1 still grants — per-shard failure containment
    assert p.alloc(1, 1) == [6]
    p.check()


def test_pool_shard_divisibility_enforced():
    with pytest.raises(ValueError):
        PagePool(10, 8, num_shards=4)  # 10 pages don't split 4 ways
    with pytest.raises(ValueError):
        PagePool(4, 8, num_shards=4)  # a block of 1 is just a null page
    with pytest.raises(ValueError):
        PagePool(9, 8, num_shards=0)


def test_pool_single_shard_degenerate_grant_order():
    # num_shards=1 must behave bit-for-bit like the PR 4-7 pool: one
    # free list over [1, N), FIFO grant order, same stats
    p = PagePool(9, 8)
    assert p.num_shards == 1 and p.pages_per_shard == 8
    assert p.alloc(3) == [1, 2, 3]
    p.release([2])
    assert p.alloc(2) == [4, 5]
    assert p.alloc(1) == [6]
    assert p.free_ids() == [7, 8, 2]
    p.check()


def test_pool_cow_copy_stays_on_page_shard():
    p = PagePool(10, 8, num_shards=2)
    (pid,) = p.alloc(1, 1)
    p.share([pid])  # two holders -> a write must copy
    new_pid, copied = p.cow(pid)
    assert copied and new_pid != pid and p.shard_of(new_pid) == 1
    assert p.refcount(pid) == 1  # the other holder keeps the original
    p.check()


# ---------------------------------------------------------------------------
# bytes model
# ---------------------------------------------------------------------------


def test_bytes_model_per_device_hierarchy():
    assert MCAST_MODES == mcast.MODES  # config literal stays in sync
    bm = mcast.bytes_model(100, 4, per_device=True)
    assert bm == {"unicast": 300.0, "sw_tree": 200.0, "hw": 100.0}
    # strict hierarchy hw < sw_tree < unicast for every n >= 4 (at n < 4
    # the tree IS n-1 sends) — including powers of two, where the
    # link-total model ties unicast and sw_tree
    for n in (4, 8, 16):
        bm = mcast.bytes_model(4096, n, per_device=True)
        assert bm["hw"] < bm["sw_tree"] < bm["unicast"], (n, bm)
    link = mcast.bytes_model(4096, 8)
    assert link["unicast"] == link["sw_tree"]  # the power-of-two tie
    # one device: no fabric crossed in any mode
    assert mcast.bytes_model(4096, 1, per_device=True) == {
        m: 0.0 for m in mcast.MODES}


# ---------------------------------------------------------------------------
# ServeConfig
# ---------------------------------------------------------------------------


def test_serve_config_validation():
    c = ServeConfig(num_shards=4, pages_per_shard=8)
    assert c.num_pages == 36  # 4 blocks of a null page + 8
    assert ServeConfig(pages=36, num_shards=4).num_pages == 36
    assert ServeConfig().num_pages is None  # engine sizes the default
    with pytest.raises(ValueError):
        ServeConfig(mcast_mode="carrier-pigeon")
    with pytest.raises(ValueError):
        ServeConfig(pages=34, num_shards=4)  # 34 don't split 4 ways
    with pytest.raises(ValueError):
        ServeConfig(pages=34, num_shards=4, pages_per_shard=8)  # contradiction
    with pytest.raises(ValueError):
        ServeConfig(cache_len=60, page_size=16)  # not page-aligned
    with pytest.raises(ValueError):
        ServeConfig(chaos=("no.such.site",))  # fault site validated here


def test_serve_config_argparse_roundtrip():
    ap = argparse.ArgumentParser()
    add_serve_args(ap)
    args = ap.parse_args(
        ["--num-shards", "4", "--pages-per-shard", "8",
         "--mcast-mode", "sw_tree", "--kv-guard", "--chaos", "pool.alloc:0.2"])
    c = serve_config.from_args(args, max_slots=3)
    assert c.num_shards == 4 and c.pages_per_shard == 8
    assert c.mcast_mode == "sw_tree" and c.kv_guard and c.max_slots == 3
    assert c.chaos == ("pool.alloc:0.2",)
    assert c.fault_plan() is not None
    # unset flags fall through to the dataclass defaults
    c0 = serve_config.from_args(ap.parse_args([]))
    assert c0 == ServeConfig()
    with pytest.raises(SystemExit):
        ap.parse_args(["--mcast-mode", "bogus"])  # choices from the field


def test_legacy_kwargs_warn_once_per_call_site(small):
    cfg, params = small
    serve_config._LEGACY_WARNED.clear()  # earlier tests may have tripped it

    def mk():  # one fixed call site, hit repeatedly
        return PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=16)

    with pytest.warns(DeprecationWarning, match="config=ServeConfig"):
        mk()
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # same call site again: no warning
        mk()
    # a *different* call site warns afresh — a long-lived session that
    # grows a new legacy caller still hears about it
    with pytest.warns(DeprecationWarning, match="config=ServeConfig"):
        PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=16)
    assert len(serve_config._LEGACY_WARNED) == 2  # (module, lineno) keyed
    with pytest.raises(TypeError):
        PagedEngine(cfg, params, max_batch=2,
                    config=ServeConfig(max_slots=2))  # both styles at once
    with pytest.raises(TypeError):
        PagedEngine(cfg, params, max_btach=2)  # typo'd legacy keyword


def test_config_engine_bitwise_matches_legacy(small):
    cfg, params = small
    reqs = _mk_requests(cfg, shared_prefix=16, n=4)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        old = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=8)
    old_out = {r.rid: r.out for r in old.run(_clone(reqs))}
    new = PagedEngine(cfg, params,
                      config=ServeConfig(max_slots=2, cache_len=64, page_size=8))
    new_out = {r.rid: r.out for r in new.run(_clone(reqs))}
    assert new_out == old_out
    assert new.flat_stats() == old.flat_stats()  # same counters, bit for bit
    new.check()


# ---------------------------------------------------------------------------
# sharded engine == dense oracle
# ---------------------------------------------------------------------------


def test_sharded_matches_dense_cold(small):
    cfg, params = small
    reqs = _mk_requests(cfg, n=5)
    dense = {r.rid: r.out for r in
             Server(cfg, params, max_batch=2, cache_len=64).run(_clone(reqs))}
    eng = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=16, num_shards=4,
        pages_per_shard=8))
    paged = {r.rid: r.out for r in eng.run(_clone(reqs))}
    assert paged == dense
    st = eng.stats()
    assert st["broadcast_chains"] == 0  # cold: nothing cached to broadcast
    eng.check()


def test_sharded_shared_prefix_broadcasts_not_reprefills(small):
    cfg, params = small
    n, prefix_len, ps = 4, 32, 8
    reqs = _mk_requests(cfg, shared_prefix=prefix_len, n=n)
    dense = {r.rid: r.out for r in
             Server(cfg, params, max_batch=2, cache_len=64).run(_clone(reqs))}
    eng = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=ps, num_shards=4,
        pages_per_shard=8))
    paged = {r.rid: r.out for r in eng.run(_clone(reqs))}
    assert paged == dense
    st = eng.stats()
    n_prefix_pages = prefix_len // ps
    # the prefix chain was prefilled ONCE (on request 0's shard), then
    # broadcast to each of the other 3 shards as they admitted a sharing
    # request — never re-prefilled
    assert st["broadcast_chains"] == n - 1
    assert st["broadcast_pages"] == (n - 1) * n_prefix_pages
    assert st["prefix_hit_tokens"] == (n - 1) * prefix_len
    assert st["broadcast_payload_bytes"] == \
        st["broadcast_pages"] * eng.page_nbytes
    # fabric accounting follows the per-device model for the mode
    mult = mcast.bytes_model(1, 4, per_device=True)[eng.mcast_mode]
    assert st["broadcast_fabric_bytes"] == \
        st["broadcast_payload_bytes"] * mult
    eng.check()


def test_sharded_tokens_identical_to_single_shard(small):
    cfg, params = small
    reqs = _mk_requests(cfg, shared_prefix=24, n=4, max_new=6)
    one = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=8, pages=33))
    o1 = {r.rid: r.out for r in one.run(_clone(reqs))}
    four = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=8, num_shards=4,
        pages_per_shard=8))
    o4 = {r.rid: r.out for r in four.run(_clone(reqs))}
    assert o4 == o1  # decode math is row/page-placement independent
    one.check()
    four.check()


def test_engine_default_pool_fills_whole_shards(small):
    cfg, params = small
    eng = PagedEngine(cfg, params,
                      config=ServeConfig(max_slots=2, cache_len=64,
                                         page_size=8, num_shards=3))
    assert eng.pool.num_pages % 3 == 0
    assert eng.pool.pages_per_shard >= 64 // 8  # each shard fits a request


# ---------------------------------------------------------------------------
# cross-shard fork / COW
# ---------------------------------------------------------------------------


def test_fork_across_shards_cow_lands_on_child_shard(small):
    cfg, params = small
    eng = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=3, cache_len=64, page_size=8, num_shards=2,
        pages_per_shard=8))
    parent = Request(rid=0, prompt=list(range(10, 22)), max_new=6, shard=0)
    assert eng._admit(parent)
    (pslot,) = eng.slots
    pages = list(eng.slots[pslot].pages)
    # same-shard fork: a zero-copy share; its first write copies-on-write
    # onto the same shard
    twin = eng.fork(pslot, Request(rid=2, prompt=list(parent.prompt),
                                   max_new=6))
    tst = eng.slots[twin]
    assert tst.pages == pages and tst.shard == 0
    need = tst.length // eng.page_size
    assert eng.pool.refcount(pages[need]) >= 2
    assert eng._ensure_writable(twin)
    assert tst.pages[need] != pages[need]
    assert eng.pool.shard_of(tst.pages[need]) == 0
    assert eng.n_cow == 1
    # cross-shard fork: a slot's pages all live on its own shard, so the
    # parent's chain is broadcast into fresh pages on the child's shard
    child_req = Request(rid=1, prompt=list(parent.prompt), max_new=6)
    cslot = eng.fork(pslot, child_req, shard=1)
    assert cslot is not None
    cst = eng.slots[cslot]
    assert cst.shard == 1
    assert len(cst.pages) == len(pages)
    assert {eng.pool.shard_of(pid) for pid in cst.pages} == {1}
    assert all(eng.pool.refcount(pid) == 1 for pid in cst.pages)
    assert eng.n_broadcast_chains == 1
    assert eng.n_broadcast_pages == len(pages)
    assert eng._ensure_writable(cslot)  # exclusively owned: no copy
    assert eng.n_cow == 1
    # both lineages decode to the same greedy continuation
    done = {r.rid: r.out for r in eng.run([])}
    assert done[0] == done[1] == done[2]
    eng.check()


# ---------------------------------------------------------------------------
# per-shard watermark + preemption
# ---------------------------------------------------------------------------


def test_scheduler_per_shard_watermark():
    pool = PagePool(10, 8, num_shards=2)
    sched = Scheduler(pool, PrefixCache(pool), watermark=1)
    # global headroom is plentiful, but shard 0 is the one that pays
    pool.alloc(3, 0)
    assert sched.can_admit(1, shard=1)
    assert not sched.can_admit(1, shard=0)  # would dip into the reserve
    rej = sched.check_admission(1, shard=0)
    assert isinstance(rej, Rejected) and rej.reason == "watermark"
    assert sched.check_admission(1, shard=1) is None
    rej = sched.check_admission(5, shard=1)  # exceeds the whole shard
    assert isinstance(rej, Rejected) and rej.reason == "pool-dry"
    assert sched.check_admission(1) is None  # shard-blind view still fine


def test_preemption_restricted_to_pressured_shard(small):
    cfg, params = small
    mk = lambda: [  # noqa: E731 — three pinned requests, two per-shard roles
        Request(rid=0, prompt=list(range(30, 39)), max_new=10, shard=0),
        Request(rid=1, prompt=list(range(40, 49)), max_new=10, shard=0),
        Request(rid=2, prompt=list(range(50, 59)), max_new=10, shard=1),
    ]
    tight = ServeConfig(max_slots=3, cache_len=64, page_size=8,
                        num_shards=2, pages_per_shard=4, watermark=0)
    eng = PagedEngine(cfg, params, config=tight)
    a, b, c = mk()
    assert eng._admit(a) and eng._admit(b) and eng._admit(c)
    # the victim for shard-0 pressure is the youngest shard-0 slot (rid
    # 1), never the younger shard-1 slot (rid 2) whose pages can't help
    by_rid = {st.req.rid: s for s, st in eng.slots.items()}
    assert eng._pick_victim(shard=0) == by_rid[1]
    assert eng._pick_victim(shard=1) == by_rid[2]
    done = {r.rid: r.out for r in eng.run([])}
    st = eng.stats()
    assert st["preempted"] >= 1  # shard 0 ran dry mid-decode
    # parity oracle: an unpressured sharded engine decodes identically
    roomy = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=3, cache_len=64, page_size=8, num_shards=2,
        pages_per_shard=16, watermark=0))
    expect = {r.rid: r.out for r in roomy.run(mk())}
    assert roomy.stats()["preempted"] == 0
    assert done == expect  # preempt/swap-in restored pages bit-identically
    eng.check()


# ---------------------------------------------------------------------------
# per-shard prefix copies + eviction
# ---------------------------------------------------------------------------


def test_prefix_per_shard_copies_broadcast_and_evict():
    pool = PagePool(10, 8, num_shards=2)
    cache = PrefixCache(pool)
    toks = list(range(17))  # 2 full shareable pages + the decode page
    p0 = pool.alloc(2, 0)
    cache.insert(toks, p0, shard=0)
    pool.release(p0)  # request retires; the tree keeps the chain
    # shard 1 has no local copy yet
    assert cache.match(toks, shard=1) == ([], 0)
    remote = cache.remote_continuation(toks, shard=1, n_local=0)
    assert [pid for _, pid in remote] == p0
    p1 = pool.alloc(2, 1)
    cache.commit_broadcast([n for n, _ in remote], 1, p1)
    pool.release(p1)  # the broadcasting consumer retires too
    got, n = cache.match(toks, shard=1)
    assert got == p1 and n == 16  # later shard-1 consumers hit locally
    pool.release(got)
    cache.pool.check([cache.pages()])
    # eviction is per-copy: dropping shard 1's copies leaves shard 0's
    assert cache.evictable_pages(shard=1) == 2
    assert cache.evict(2, shard=1) == 2
    assert pool.free_pages_on(1) == 4
    assert cache.match(toks, shard=0)[1] == 16  # shard 0 chain intact
    pool.release(p0)
    pool.check([cache.pages()])


# ---------------------------------------------------------------------------
# metrics schema
# ---------------------------------------------------------------------------


def test_snapshot_schema_includes_broadcast_surface(small):
    cfg, params = small
    snap = ServeMetrics().snapshot()
    validate_snapshot(snap)  # required keys present even with no engine
    assert snap["num_shards"] == 1 and snap["mcast_mode"] == "unicast"
    assert snap["broadcast_pages"] == 0
    eng = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=8, num_shards=4,
        pages_per_shard=8, mcast_mode="sw_tree"))
    eng.run(_mk_requests(cfg, shared_prefix=32, n=4))
    snap = validate_snapshot(ServeMetrics().snapshot(engine=eng))
    assert snap["num_shards"] == 4 and snap["mcast_mode"] == "sw_tree"
    assert snap["broadcast_pages"] == 12
    for s in range(4):
        assert snap[f"shard{s}_free_pages"] + snap[f"shard{s}_in_use"] == 8
    # the schema still rejects junk (incl. a wrongly-typed mode)
    with pytest.raises(ValueError):
        validate_snapshot({**snap, "mcast_mode": 3})
    with pytest.raises(ValueError):
        validate_snapshot({**snap, "made_up_metric": 1})


@pytest.mark.parametrize("num_shards", [1, 4])
def test_stats_delta_shard_gauges_round_trip(small, num_shards):
    """Regression: the whole ``shard{s}_*`` family must be treated as
    gauges — a second quiet window reports each shard's *current*
    occupancy, not a (zero) counter difference, for S=1 and S=4 alike."""
    cfg, params = small
    eng = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=8, num_shards=num_shards,
        pages_per_shard=8 if num_shards > 1 else None))
    eng.run(_mk_requests(cfg, shared_prefix=16, n=4))
    d1 = eng.stats_delta()
    assert d1["pool_allocated"] > 0
    # quiet second window: counters zero, every gauge = current value
    d2 = eng.stats_delta()
    now = eng.flat_stats()
    assert d2["pool_allocated"] == 0 and d2["pool_freed"] == 0
    for s in range(num_shards):
        assert d2[f"shard{s}_free_pages"] == now[f"shard{s}_free_pages"]
        assert d2[f"shard{s}_in_use"] == now[f"shard{s}_in_use"]
        assert (d2[f"shard{s}_free_pages"] + d2[f"shard{s}_in_use"]
                == eng.pool.pages_per_shard)
    assert d2["free_pages"] == eng.pool.free_pages


# ---------------------------------------------------------------------------
# chaos: one shard's alloc fault degrades without corrupting the others
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_shard_alloc_fault_contained_and_token_identical(small):
    from repro.serve import Fault, FaultPlan

    cfg, params = small
    shards = [0, 0, 1, 1]
    sharded = ServeConfig(max_slots=3, cache_len=64, page_size=8,
                          num_shards=2, pages_per_shard=12, kv_guard=True)
    reqs = _mk_requests(cfg, shared_prefix=16, n=4, shards=shards)
    calm = PagedEngine(cfg, params, config=sharded)
    expect = {r.rid: r.out for r in calm.run(_clone(reqs))}
    eng = PagedEngine(cfg, params, config=sharded)
    plan = FaultPlan([Fault("pool.alloc", at=1, count=2)])
    with plan:
        done = {r.rid: r.out for r in eng.run(_clone(reqs))}
    assert plan.fired  # the injected exhaustion actually hit a shard
    # degraded shard recovered; the other shard's requests untouched —
    # every token stream identical to the fault-free run
    assert done == expect
    assert len(done) == len(reqs)
    eng.check()
