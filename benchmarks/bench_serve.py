"""Serving-path benchmarks: paged decode throughput + prefix-cache
prefill latency (shared-prefix vs. cold workload mix) + the
chunked-prefill supertile kernel.

``kernel_``-prefixed rows ride the existing >15% regression gate in
``benchmarks/check_regression.py`` (reduced-model reference-backend
timings — the same CPU-CI numerics the serve smoke job runs — plus
interpret-mode timings for the forced-pallas kernel rows):

* ``kernel_serve_paged_decode``   — end-to-end engine decode steps for a
  full batch against ~528-token paged contexts: the serving throughput
  number (derived column reports tokens/s).
* ``kernel_paged_decode_int8``    — the same decode workload on int8
  pages (dequant-on-gather): the halved-HBM serving configuration must
  not regress relative to its bf16 sibling.
* ``kernel_serve_guard_overhead`` — the bf16 decode workload with the
  PR-6 robustness guards armed (``kv_guard`` fingerprints +
  ``kernel_fallback`` non-finite check and undonated cache buffers);
  the derived column reports the overhead vs. the unguarded row and
  asserts it stays under 5%.
* ``kernel_serve_trace_overhead`` — the bf16 decode workload with the
  ``repro.obs`` tracing recorder armed (the engine.step, decode.prepare,
  engine.decode and engine.sample spans, each a profiler annotation too,
  plus pool/prefix instants per step); the derived column reports the
  overhead vs. the untraced row and asserts it stays under 5%.
* ``kernel_serve_prefill_cold``   — admission latency for a cold
  (prefix-miss) prompt: the whole prompt runs through the model.
* ``kernel_serve_prefill_hit``    — admission latency for a prompt
  sharing a 512-token cached prefix: only the divergent suffix runs.
  The derived column records the hit/cold speedup and asserts the
  multicast invariant — the shared prefix's pages were allocated
  exactly once for the whole batch.
* ``kernel_serve_mcast_bytes``    — 4-shard pool, shared-prefix round:
  one local prefill + three page-chain broadcasts (sw_tree timed); the
  derived column reports analytic fabric bytes per mcast mode and
  asserts the paper's per-device hierarchy hw < sw_tree < unicast.
* ``kernel_paged_prefill_pallas`` — the chunked-prefill supertile kernel
  (forced pallas, interpret mode) on a multi-token suffix problem: one
  K/V page fetch multicast across the q chunk.
* ``kernel_paged_prefill_ref``    — the same problem through the
  reference gather backend (the CPU-CI serving path); the derived
  column records the interpret/reference ratio for context.

``run(only=...)`` skips whole sections whose rows are filtered out, so
``benchmarks.run --only`` can re-measure a single regressed row without
paying for the engine workloads.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

REPS = 12
PREFIX_LEN = 512
SUFFIX_LEN = 16
PAGE_SIZE = 16
DECODE_STEPS_PER_CALL = 4

# the supertile-kernel rows: one batch of bucket-padded 64-token
# suffixes against 256-token paged contexts (sized so the interpret-mode
# pallas row stays ~1s/call and the reference row clears the gate's 5ms
# floor), timed with fewer reps than the engine rows — interpret-mode
# seconds-per-call amortise the scheduler jitter the rep count fights
PF_B, PF_S, PF_H, PF_KVH, PF_D = 4, 64, 8, 4, 64
PF_PAGES = 16  # pages/seq -> 256-token context at PAGE_SIZE
PF_REPS = 5


def run(only: str | None = None) -> list[str]:
    from repro import kernels
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import PagedEngine, Request

    def want(*names: str) -> bool:
        return only is None or any(only in n for n in names)

    rows: dict[str, str] = {}

    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prefix = list(rng.integers(0, cfg.vocab, size=PREFIX_LEN))

    def mk_engine(batch=8, kv_dtype="bf16", **guard_kw):
        # pool sized to the workload: per-call latency includes one
        # functional rewrite of the pools, so a vastly oversized pool
        # would benchmark memcpy instead of serving
        return PagedEngine(
            cfg, params, max_batch=batch, cache_len=1024, page_size=PAGE_SIZE,
            num_pages=384, kv_dtype=kv_dtype, **guard_kw,
        )

    def decode_row(kv_dtype: str, **guard_kw) -> tuple[float, float]:
        """(best_us, tok/s) for 8 shared-prefix requests decoding."""
        eng = mk_engine(kv_dtype=kv_dtype, **guard_kw)
        reqs = [
            Request(rid=i,
                    prompt=prefix + list(rng.integers(0, cfg.vocab,
                                                      size=SUFFIX_LEN)),
                    max_new=400)  # never finishes during timing: pure decode
            for i in range(8)
        ]
        base_alloc = eng.pool.stats.allocated
        for r in reqs:
            assert eng._admit(r)
        if kv_dtype == "bf16":
            prefix_pages = PREFIX_LEN // PAGE_SIZE
            # the multicast invariant the ISSUE gates on: 8 shared-prefix
            # requests, prefix pages allocated exactly once
            suffix_pages = -(-(SUFFIX_LEN + 1) // PAGE_SIZE)
            expected = prefix_pages + 8 * suffix_pages
            got_alloc = eng.pool.stats.allocated - base_alloc
            assert got_alloc == expected, (got_alloc, expected)
            assert eng.prefix.hit_tokens == 7 * PREFIX_LEN
        eng.step()  # compile the decode program
        best = float("inf")
        for _ in range(REPS):
            t0 = time.perf_counter()
            for _ in range(DECODE_STEPS_PER_CALL):
                eng.step()
            best = min(best, time.perf_counter() - t0)
        return best * 1e6, 8 * DECODE_STEPS_PER_CALL / best

    # -- decode throughput: 8 requests sharing the 512-token prefix ---------
    if want("kernel_serve_paged_decode", "kernel_serve_guard_overhead",
            "kernel_serve_trace_overhead"):
        decode_us, toks_per_s = decode_row("bf16")
        if want("kernel_serve_paged_decode"):
            rows["kernel_serve_paged_decode"] = (
                f"kernel_serve_paged_decode,{decode_us:.1f},"
                f"b8 ctx~{PREFIX_LEN + SUFFIX_LEN} {DECODE_STEPS_PER_CALL} "
                f"steps -> {toks_per_s:.0f} tok/s (paged pool ps={PAGE_SIZE})"
            )
        if want("kernel_serve_guard_overhead"):
            # same workload with every PR-6 detector armed: chain
            # fingerprints (admission-time, not in this loop's hot path),
            # the per-step non-finite logits check, and undonated cache
            # buffers (the price of keeping fallback retries possible)
            guard_us, _ = decode_row(
                "bf16", kv_guard=True, kernel_fallback=True
            )
            overhead = (guard_us - decode_us) / decode_us * 100.0
            assert overhead < 5.0, (guard_us, decode_us, overhead)
            rows["kernel_serve_guard_overhead"] = (
                f"kernel_serve_guard_overhead,{guard_us:.1f},"
                f"decode with kv-guard + kernel-fallback armed: "
                f"{overhead:+.1f}% vs unguarded (gate <5%)"
            )
        if want("kernel_serve_trace_overhead"):
            # same workload with the obs recorder armed: per step, four
            # spans (engine.step and its decode phases), each also a
            # profiler annotation, and the release instants
            from repro.obs import trace as obs_trace

            with obs_trace.tracing(max_events=1 << 16):
                traced_us, _ = decode_row("bf16")
            t_overhead = (traced_us - decode_us) / decode_us * 100.0
            assert t_overhead < 5.0, (traced_us, decode_us, t_overhead)
            rows["kernel_serve_trace_overhead"] = (
                f"kernel_serve_trace_overhead,{traced_us:.1f},"
                f"decode with obs tracing armed: "
                f"{t_overhead:+.1f}% vs untraced (gate <5%)"
            )

    if want("kernel_paged_decode_int8"):
        int8_us, int8_tps = decode_row("int8")
        rows["kernel_paged_decode_int8"] = (
            f"kernel_paged_decode_int8,{int8_us:.1f},"
            f"b8 ctx~{PREFIX_LEN + SUFFIX_LEN} {DECODE_STEPS_PER_CALL} steps "
            f"-> {int8_tps:.0f} tok/s (int8 pages, dequant-on-gather)"
        )

    # -- prefill latency: cold (full prompt) vs. prefix hit (suffix) --------
    if want("kernel_serve_prefill_cold", "kernel_serve_prefill_hit"):
        def admit_once(engine, prompt):
            req = Request(rid=0, prompt=prompt, max_new=400)
            t0 = time.perf_counter()
            assert engine._admit(req)
            dt = time.perf_counter() - t0
            (slot,) = [s for s, st in engine.slots.items() if st.req is req]
            st = engine.slots.pop(slot)
            engine.pool.release(st.pages)
            return dt

        eng2 = mk_engine(batch=1)
        cold_prompt = prefix + list(rng.integers(0, cfg.vocab, size=SUFFIX_LEN))
        admit_once(eng2, list(cold_prompt))  # compile both bucket programs
        admit_once(eng2, list(cold_prompt))

        best_hit = float("inf")
        for _ in range(REPS):  # the prefix chain stays cached between reps
            suffix = list(rng.integers(0, cfg.vocab, size=SUFFIX_LEN))
            best_hit = min(best_hit, admit_once(eng2, prefix + suffix))

        best_cold = float("inf")
        for i in range(REPS):
            # unique head token (mod vocab: stays a real token id, and
            # never wraps back onto prefix[0] for i < vocab - 1)
            # -> guaranteed prefix miss, same length bucket
            prompt = [(int(prefix[0]) + 1 + i) % cfg.vocab] + prefix[1:] + list(
                rng.integers(0, cfg.vocab, size=SUFFIX_LEN)
            )
            best_cold = min(best_cold, admit_once(eng2, prompt))
            eng2.prefix.evict(len(eng2.prefix))  # keep the pool from filling

        total = PREFIX_LEN + SUFFIX_LEN
        speedup = best_cold / best_hit
        # a hit prefills 16 of 528 tokens (33x fewer prefill FLOPs); wall
        # clock must reflect a healthy slice of that
        assert speedup > 2.0, (best_cold, best_hit)
        rows["kernel_serve_prefill_cold"] = (
            f"kernel_serve_prefill_cold,{best_cold * 1e6:.1f},"
            f"prefix-miss prefill of {total} tokens (bucketed)"
        )
        rows["kernel_serve_prefill_hit"] = (
            f"kernel_serve_prefill_hit,{best_hit * 1e6:.1f},"
            f"shared {PREFIX_LEN}-token prefix multicast: {SUFFIX_LEN}-token "
            f"suffix only, {speedup:.1f}x faster than cold; prefix pages "
            f"allocated once for 8 requests"
        )

    # -- sharded pool: page-chain broadcast latency + fabric bytes ----------
    if want("kernel_serve_mcast_bytes"):
        from repro.dist import mcast
        from repro.serve import ServeConfig

        n_shards = 4
        prefix_pages = PREFIX_LEN // PAGE_SIZE

        def broadcast_round(eng):
            """Admit 4 shared-prefix requests (router spreads them over
            the 4 shards: one local prefill/hit + 3 page-chain
            broadcasts), then retire them and evict the non-primary
            copies so the next round broadcasts again."""
            t0 = time.perf_counter()
            for i in range(n_shards):
                req = Request(
                    rid=i,
                    prompt=prefix + list(rng.integers(0, cfg.vocab,
                                                      size=SUFFIX_LEN)),
                    max_new=400,
                )
                assert eng._admit(req)
            dt = time.perf_counter() - t0
            for slot in list(eng.slots):
                eng.pool.release(eng.slots.pop(slot).pages)
            for s in range(1, n_shards):
                eng.prefix.evict(prefix_pages, shard=s)
            return dt

        fabric = {}
        best = float("inf")
        for mode in mcast.MODES:
            eng = PagedEngine(cfg, params, config=ServeConfig(
                max_slots=n_shards, cache_len=1024, page_size=PAGE_SIZE,
                num_shards=n_shards, pages_per_shard=96, mcast_mode=mode,
            ))
            broadcast_round(eng)  # compile prefill + broadcast programs
            st = eng.stats()
            assert st["broadcast_chains"] == n_shards - 1, st
            assert st["broadcast_pages"] == (n_shards - 1) * prefix_pages, st
            fabric[mode] = st["broadcast_fabric_bytes"]
            if mode == "sw_tree":  # the timed production-ish mode
                for _ in range(REPS):
                    best = min(best, broadcast_round(eng))
        # the paper's hierarchy, per-device: one hw fabric transaction
        # beats log2(n) tree hops beats n-1 unicast replications
        assert fabric["hw"] < fabric["sw_tree"] < fabric["unicast"], fabric
        rows["kernel_serve_mcast_bytes"] = (
            f"kernel_serve_mcast_bytes,{best * 1e6:.1f},"
            f"4-shard shared-prefix round: {prefix_pages}-page chain x3 "
            f"broadcasts (sw_tree); fabric MB uni/tree/hw "
            f"{fabric['unicast'] / 1e6:.1f}/{fabric['sw_tree'] / 1e6:.1f}"
            f"/{fabric['hw'] / 1e6:.1f}"
        )

    # -- chunked-prefill supertile kernel vs. reference gather ---------------
    if want("kernel_paged_prefill_pallas", "kernel_paged_prefill_ref"):
        k = jax.random.PRNGKey(1)
        ks = jax.random.split(k, 3)
        num_pages = 1 + PF_B * PF_PAGES
        q = jax.random.normal(ks[0], (PF_B, PF_S, PF_H, PF_D), jnp.float32)
        kp = jax.random.normal(
            ks[1], (1, PF_KVH, num_pages, PAGE_SIZE, PF_D), jnp.float32
        )
        vp = jax.random.normal(
            ks[2], (1, PF_KVH, num_pages, PAGE_SIZE, PF_D), jnp.float32
        )
        table = jnp.arange(1, 1 + PF_B * PF_PAGES, dtype=jnp.int32) \
            .reshape(PF_B, PF_PAGES)
        lengths = jnp.full((PF_B,), PF_PAGES * PAGE_SIZE, jnp.int32)
        start = lengths - PF_S  # a full-bucket suffix at the context tail
        paged = kernels.op("paged_attention")
        res = kernels.resolve(
            "paged_attention",
            (PF_B, PF_S, PF_H, PF_KVH, PF_PAGES, PAGE_SIZE, PF_D, 0),
            jnp.float32, policy="pallas",
        )
        pallas_fn = lambda: paged(q, kp, vp, table, start, lengths, 0,  # noqa: E731
                                  policy="pallas")
        ref_fn = lambda: paged(q, kp, vp, table, start, lengths, 0,  # noqa: E731
                               policy="reference")
        for fn in (pallas_fn, ref_fn):
            fn().block_until_ready()  # compile
        best = {"pallas": float("inf"), "ref": float("inf")}
        for _ in range(PF_REPS):  # interleaved: load spikes hit both alike
            for name, fn in (("pallas", pallas_fn), ("ref", ref_fn)):
                t0 = time.perf_counter()
                fn().block_until_ready()
                best[name] = min(best[name], time.perf_counter() - t0)
        shape = (f"b{PF_B} s{PF_S} h{PF_H}/kv{PF_KVH} d{PF_D} "
                 f"ctx{PF_PAGES * PAGE_SIZE} ps{PAGE_SIZE}")
        rows["kernel_paged_prefill_pallas"] = (
            f"kernel_paged_prefill_pallas,{best['pallas'] * 1e6:.1f},"
            f"supertile chunked prefill (interpret) {shape} "
            f"sched={res.schedule} qc={res.cfg.get('qc')}"
        )
        rows["kernel_paged_prefill_ref"] = (
            f"kernel_paged_prefill_ref,{best['ref'] * 1e6:.1f},"
            f"reference gather {shape}; interpret/ref ratio "
            f"{best['pallas'] / best['ref']:.1f}x"
        )

    return list(rows.values())
