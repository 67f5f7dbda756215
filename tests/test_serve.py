"""Serving-loop tests: continuous batching + decode consistency, dense
ring-buffer fallback vs. the paged (prefix-sharing) engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import Request, Server
from repro.models import lm
from repro.serve import PagedEngine

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small():
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = lm.init(cfg, KEY)
    return cfg, params


def _greedy_reference(cfg, params, prompt, n_new):
    """Sequential full-forward greedy decode (no cache) — the oracle."""
    toks = list(prompt)
    out = []
    for _ in range(n_new):
        logits, _ = lm.forward(params, cfg, jnp.asarray(toks, jnp.int32)[None])
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


@pytest.mark.slow
def test_server_matches_uncached_greedy(small):
    cfg, params = small
    server = Server(cfg, params, max_batch=2, cache_len=64)
    prompts = [[5, 9, 2, 7], [11, 3, 8, 1, 4]]
    reqs = [Request(rid=i, prompt=p, max_new=5) for i, p in enumerate(prompts)]
    done = {r.rid: r for r in server.run(reqs)}
    for i, p in enumerate(prompts):
        ref = _greedy_reference(cfg, params, p, 5)
        assert done[i].out == ref, f"req {i}: {done[i].out} != {ref}"


def test_continuous_batching_all_served(small):
    cfg, params = small
    server = Server(cfg, params, max_batch=2, cache_len=64)
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=list(rng.integers(0, cfg.vocab, size=4 + i)), max_new=4)
        for i in range(5)  # more requests than slots -> queueing
    ]
    done = server.run(reqs)
    assert len(done) == 5
    assert all(len(r.out) == 4 for r in done)


# ---------------------------------------------------------------------------
# paged engine vs. dense fallback
# ---------------------------------------------------------------------------


def _mk_requests(cfg, *, shared_prefix=0, n=4, max_new=5, seed=7):
    rng = np.random.default_rng(seed)
    prefix = list(rng.integers(0, cfg.vocab, size=shared_prefix))
    return [
        Request(rid=i, prompt=prefix + list(rng.integers(0, cfg.vocab, size=3 + i)),
                max_new=max_new)
        for i in range(n)
    ]


def _clone(reqs):
    return [Request(rid=r.rid, prompt=list(r.prompt), max_new=r.max_new)
            for r in reqs]


def test_paged_matches_dense_cold(small):
    cfg, params = small
    reqs = _mk_requests(cfg, n=5)
    dense = {r.rid: r.out for r in
             Server(cfg, params, max_batch=2, cache_len=64).run(_clone(reqs))}
    eng = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=16)
    paged = {r.rid: r.out for r in eng.run(_clone(reqs))}
    assert paged == dense
    eng.check()  # refcount/free-list audit: no page leaked by the run


def test_paged_matches_dense_with_shared_prefix(small):
    cfg, params = small
    reqs = _mk_requests(cfg, shared_prefix=32, n=4)
    dense = {r.rid: r.out for r in
             Server(cfg, params, max_batch=2, cache_len=64).run(_clone(reqs))}
    eng = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=8)
    paged = {r.rid: r.out for r in eng.run(_clone(reqs))}
    assert paged == dense
    st = eng.stats()
    # the 32-token prefix (4 pages of 8) prefilled once, multicast to
    # the other 3 requests
    assert st["prefix_hit_tokens"] == 3 * 32
    assert st["prefix_pages"] >= 4
    eng.check()


@pytest.mark.parametrize("pressure", [False, True],
                         ids=["shared_prefix", "preempting"])
def test_packed_heads_serve_like_one_head_per_row(small, monkeypatch,
                                                  pressure):
    """head_dim 64 with 2 kv heads: the pool stores both heads side by
    side in each 128-lane row.  Prefix sharing with the COW of a shared
    partial page, and preemption's swap round trip under a pool too
    small for two requests, serve the same tokens from it as from a
    pool with one head per row."""
    import dataclasses

    from repro.configs.base import AttnConfig
    from repro.nn import attention

    cfg = dataclasses.replace(small[0], attn=AttnConfig(
        n_heads=4, n_kv_heads=2, head_dim=64, qkv_bias=True))
    params = lm.init(cfg, KEY)
    reqs = _mk_requests(cfg, n=3, max_new=10, seed=3) if pressure \
        else _mk_requests(cfg, shared_prefix=20, n=4)
    kw = dict(page_size=4, num_pages=7, watermark=1) if pressure \
        else dict(page_size=8)

    def serve():
        eng = PagedEngine(cfg, params, max_batch=2, cache_len=64, **kw)
        out = {r.rid: r.out for r in eng.run(_clone(reqs))}
        eng.check()
        return eng, out

    eng, packed = serve()
    assert eng.caches["stage0"]["b0"].k_pages.shape[1:] == (
        1, eng.pool.num_pages, kw["page_size"], 128)
    if pressure:
        assert eng.n_preempted > 0
    else:
        assert eng.stats()["prefix_hit_tokens"] >= 3 * 16
    monkeypatch.setattr(attention, "packed_heads", lambda kvh, hd: 1)
    eng, unpacked = serve()
    assert eng.caches["stage0"]["b0"].k_pages.shape[1] == 2
    assert packed == unpacked


def test_prefix_pages_allocated_exactly_once(small):
    cfg, params = small
    n, prefix_len, ps = 4, 32, 8
    reqs = _mk_requests(cfg, shared_prefix=prefix_len, n=n, max_new=3)
    eng = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=ps)
    eng.run(reqs)
    # every allocation beyond request 0's is suffix/decode-only: the
    # prefix pages were granted exactly once and shared thereafter.
    # A request writes positions [0, len+max_new-1) (the final sampled
    # token is never fed back); admission pre-allocates through len+1.
    expected = sum(
        max(-(-(len(r.prompt) + 1) // ps),
            -(-(len(r.prompt) + r.max_new - 1) // ps))
        for r in reqs
    ) - (n - 1) * (prefix_len // ps)
    assert eng.pool.stats.allocated == expected
    assert eng.pool.stats.shared >= (n - 1) * (prefix_len // ps)
    eng.check()


def test_preemption_restores_pages_bit_identically(small):
    cfg, params = small
    eng = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=8)
    reqs = _mk_requests(cfg, n=2, max_new=4)
    assert eng._admit(reqs[0]) and eng._admit(reqs[1])
    slot = 1
    st = eng.slots[slot]
    n_pages = len(st.pages)
    before = jax.device_get(
        eng._gather_pages(eng.caches, eng._pages_ids_fixed(st.pages))
    )
    eng._preempt(slot)
    assert reqs[1]._swap is not None and eng.pool.stats.freed >= n_pages
    # dirty the freed pages: restore must come from the host copy
    got = eng.pool.alloc(n_pages)
    eng.caches = eng._scatter_pages(
        eng.caches, eng._pages_ids_fixed(got),
        jax.tree.map(lambda a: np.full_like(a, -1),
                     jax.device_get(eng._gather_pages(
                         eng.caches, eng._pages_ids_fixed(got)))),
    )
    eng.pool.release(got)
    assert eng._swap_in(slot, reqs[1])
    st2 = eng.slots[slot]
    after = jax.device_get(
        eng._gather_pages(eng.caches, eng._pages_ids_fixed(st2.pages))
    )
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a[:, :, :n_pages], b[:, :, :n_pages])
    eng.check()


def test_preemption_under_pressure_end_to_end(small):
    cfg, params = small
    reqs = _mk_requests(cfg, n=3, max_new=10, seed=3)
    dense = {r.rid: r.out for r in
             Server(cfg, params, max_batch=2, cache_len=64).run(_clone(reqs))}
    # pool too small for two full requests -> decode page faults preempt
    eng = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=4,
                      num_pages=7, watermark=1)
    paged = {r.rid: r.out for r in eng.run(_clone(reqs))}
    assert eng.n_preempted > 0
    assert {rid: out for rid, out in paged.items()} == dense
    eng.check()


def test_fork_copy_on_write(small):
    cfg, params = small
    eng = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=8)
    parent = Request(rid=0, prompt=[5, 9, 2, 7, 11, 3], max_new=6)
    assert eng._admit(parent)
    child = Request(rid=1, prompt=list(parent.prompt), max_new=6)
    slot = eng.fork(0, child)
    assert slot is not None
    tail = eng.slots[0].pages[-1]
    assert eng.pool.refcount(tail) >= 2  # shared until someone writes
    done = {}
    while len(done) < 2:
        for r in eng.step():
            done[r.rid] = r.out
    assert eng.n_cow >= 1  # divergence copied the shared tail page
    assert done[0] == done[1]  # identical state -> identical greedy tokens
    eng.check()


@pytest.mark.parametrize("chunk", [2, 3, 16])
def test_chunked_prefill_matches_dense_and_unchunked(small, chunk):
    """Chunked suffix prefill is invisible: any chunk size produces the
    exact token streams of the unchunked paged engine (and of the dense
    fallback on this workload)."""
    cfg, params = small
    reqs = _mk_requests(cfg, shared_prefix=32, n=4)
    dense = {r.rid: r.out for r in
             Server(cfg, params, max_batch=2, cache_len=64).run(_clone(reqs))}
    un = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=8)
    unchunked = {r.rid: r.out for r in un.run(_clone(reqs))}
    assert unchunked == dense
    eng = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=8,
                      prefill_chunk=chunk)
    chunked = {r.rid: r.out for r in eng.run(_clone(reqs))}
    assert chunked == unchunked
    # chunking must not change the page accounting either
    assert eng.pool.stats.allocated == un.pool.stats.allocated
    assert eng.stats()["prefix_hit_tokens"] == un.stats()["prefix_hit_tokens"]
    un.check()
    eng.check()


def test_chunked_prefill_int8_matches_unchunked_int8(small):
    cfg, params = small
    reqs = _mk_requests(cfg, shared_prefix=32, n=4)
    eng_a = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=8,
                        kv_dtype="int8")
    a = {r.rid: r.out for r in eng_a.run(_clone(reqs))}
    eng_b = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=8,
                        kv_dtype="int8", prefill_chunk=3)
    b = {r.rid: r.out for r in eng_b.run(_clone(reqs))}
    assert a == b
    eng_a.check()
    eng_b.check()


def test_preemption_mid_chunked_prefill_bit_identical(small):
    """A request admitted via chunked prefill survives a preempt/restore
    cycle bit-identically — the per-chunk page charging leaves the same
    pages behind as the one-shot path."""
    cfg, params = small
    eng = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=8,
                      prefill_chunk=2)
    reqs = _mk_requests(cfg, shared_prefix=16, n=2, max_new=6)
    assert eng._admit(reqs[0]) and eng._admit(reqs[1])  # req 1 chunked in
    slot = 1
    st = eng.slots[slot]
    n_pages = len(st.pages)
    before = jax.device_get(
        eng._gather_pages(eng.caches, eng._pages_ids_fixed(st.pages))
    )
    eng._preempt(slot)
    # dirty the freed pages: restore must come from the host copy
    got = eng.pool.alloc(n_pages)
    eng.caches = eng._scatter_pages(
        eng.caches, eng._pages_ids_fixed(got),
        jax.tree.map(lambda a: np.full_like(a, -1),
                     jax.device_get(eng._gather_pages(
                         eng.caches, eng._pages_ids_fixed(got)))),
    )
    eng.pool.release(got)
    assert eng._swap_in(slot, reqs[1])
    after = jax.device_get(
        eng._gather_pages(
            eng.caches, eng._pages_ids_fixed(eng.slots[slot].pages)
        )
    )
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a[:, :, :n_pages], b[:, :, :n_pages])
    eng.check()


def test_paged_engine_int8_pages_serve(small):
    cfg, params = small
    reqs = _mk_requests(cfg, n=3, max_new=4)
    eng = PagedEngine(cfg, params, max_batch=2, cache_len=64, page_size=8,
                      kv_dtype="int8")
    done = eng.run(reqs)
    assert len(done) == 3 and all(len(r.out) == 4 for r in done)
    eng.check()


def test_paged_cache_rejects_unsupported_archs():
    cfg = get_config("recurrentgemma-2b", reduced=True)  # windows + rglru
    with pytest.raises(ValueError, match="paged KV serving"):
        lm.init_paged_cache(cfg, 8, 8)
    # MoE too: expert capacity scales with the padded call length, so
    # bucketed / suffix prefills would route real tokens differently
    cfg_moe = get_config("moonshot-v1-16b-a3b", reduced=True)
    with pytest.raises(ValueError, match="paged KV serving"):
        lm.init_paged_cache(cfg_moe, 8, 8)


def test_dense_server_disables_bucketing_where_padding_is_inexact():
    cfg_moe = get_config("moonshot-v1-16b-a3b", reduced=True)
    params = lm.init(cfg_moe, KEY)
    assert Server(cfg_moe, params, max_batch=1, cache_len=32)._bucket is None
    cfg_win = get_config("recurrentgemma-2b", reduced=True)
    params = lm.init(cfg_win, KEY)
    assert Server(cfg_win, params, max_batch=1, cache_len=32)._bucket is None


def test_ring_buffer_local_cache_decode(small):
    """Local-window arch decodes correctly past the window boundary."""
    cfg = get_config("recurrentgemma-2b", reduced=True)
    params = lm.init(cfg, KEY)
    s = 24  # window in the reduced config is 16 -> wraps the ring
    toks = jax.random.randint(KEY, (1, s), 0, cfg.vocab)
    full, _ = lm.forward(params, cfg, toks)
    _, caches = lm.prefill(params, cfg, toks[:, :8], cache_slots=s)
    outs = []
    for t in range(8, s):
        lg, caches = lm.decode_step(params, cfg, caches, toks[:, t : t + 1], jnp.int32(t))
        outs.append(lg)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(full[:, 8:s], np.float32),
        rtol=5e-2, atol=5e-2,
    )
