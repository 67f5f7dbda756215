"""On-chip smoke test of the paged serving path at full qwen1.5-0.5b width.

Default phase (one TPU chip): serve a seeded Poisson trace of about ten
requests through ``ServeLoop`` over ``PagedEngine``, in-process through
``repro.launch.serve.main`` (the ``--server`` entry point), with random
bf16 weights drawn from ``--seed``.  Cold prefill, paged decode and the
``pallas_prefill`` prefix-hit path all run.  Then the prefill and first
decode logits of three requests are compared with the same requests under
the reference kernel backend on the same chip.  The same requests also
run in float32 (reference backend, matmul precision highest), which
measures how far each bf16 backend drifts from exact arithmetic, and
under two planted decode faults, which show what the comparison can see.
Last, each main-path kernel is compared with its reference on identical
full-width inputs shaped like the engine's own calls.

``--four-chip`` (four chips, one host) runs only the mesh-sharded page
pool: the same seeded trace at ``--num-shards 4 --mesh`` in every
``mcast_mode`` against one unsharded engine, all in one process; the token
streams must be identical.

The script fails (non-zero exit, no result line) when JAX finds no TPU,
when a request does not drain, when any kernel site on the path resolved
to the reference backend or fell back to it, when logits or kernel
outputs differ from the reference backend beyond the stated tolerances,
or when the planted row-swap fault reads within the logits tolerance.
On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Run from the repository root:  python chip_smoke.py [--four-chip]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1.5-0.5b"
# Per-kernel tolerance: max |pallas - reference| over max |reference|
# for one kernel call on identical full-width inputs.  Both accumulate
# in fp32 and round the output to bf16 (unit roundoff 2**-8), so they
# agree to a few bf16 ulps of the largest output (CPU, interpret mode:
# at most 0.0145); 2**-4 is 16 of them.
KERNEL_RTOL = 2**-4
# End-to-end logits tolerance, same measure, per captured logits tensor.
# With random weights a bf16 rounding difference compounds over the 24
# layers, so two bf16 evaluations that round at different points drift
# apart by up to the sum of their drifts from exact arithmetic.  The
# run measures those drifts against a float32 evaluation and prints
# them, and fails unless a planted fault (two decode rows swap block
# tables) reads above this tolerance.  On a TPU v5e with seed 0: pallas
# vs reference at most 0.427, each vs float32 at most 0.66, the planted
# faults 1.39 (a page short) and 1.72 (rows swapped).
LOGITS_RTOL = 0.6
# paged-attention schedules the default phase must dispatch (``linear``
# may use any pallas matmul schedule)
REQUIRED_SCHEDULES = (("paged_attention", "pallas"),
                      ("paged_attention", "pallas_prefill"))

# the served workload: Poisson arrivals at qps 16 for 0.75 s (10 requests
# with seed 0), prompt bodies of 64..384 tokens, half of them behind a
# 128-token shared prefix
SERVE_ARGV = [
    "--arch", ARCH, "--server", "--qps", "16", "--duration", "0.75",
    "--max-new", "32", "--max-slots", "8", "--cache-len", "2048",
    "--prompt-len", "64,384", "--shared-prefix", "128",
    "--shared-frac", "0.5", "--prefill-chunk", "64",
]
# the four-chip comparison: a shorter trace (fewer compiled buckets)
FOUR_CHIP_ARGV = [
    "--arch", ARCH, "--server", "--qps", "16", "--duration", "0.5",
    "--max-new", "16", "--max-slots", "8", "--cache-len", "1024",
    "--prompt-len", "64,128", "--shared-prefix", "128",
    "--shared-frac", "0.5", "--prefill-chunk", "64",
]


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"# {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# helpers over the obs recorder
# ---------------------------------------------------------------------------


def dispatch_spans(rec) -> list[dict]:
    return [e["args"] for e in rec.events()
            if e.get("ph") == "X" and e["name"].startswith("dispatch.")]


def check_kernels(rec) -> dict[str, dict[str, int]]:
    """Every dispatch span on the path is a pallas schedule and a linear
    (matmul) site ran; returns {op: {schedule/backend: sites}}."""
    spans = dispatch_spans(rec)
    ref = sorted({(a["op"], tuple(a["shape"])) for a in spans
                  if a["backend"] == "reference"})
    check(not ref, f"kernel sites resolved to the reference backend: {ref}")
    check(any(a["op"] == "matmul" for a in spans),
          "no linear (matmul) dispatch was recorded")
    out: dict[str, dict[str, int]] = {}
    for a in spans:
        per_op = out.setdefault(a["op"], {})
        key = f"{a['schedule']}/{a['backend']}"
        per_op[key] = per_op.get(key, 0) + 1
    return out


def check_no_fallback() -> None:
    from repro import kernels

    fb = kernels.fallback_stats()
    check(fb.fallbacks == 0, f"kernel fallbacks: {fb}")
    check(fb.substitutions == 0, f"reference substitutions: {fb}")


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


class CompileLog:
    """What this process compiled, from ``jax.monitoring``: wall seconds
    spent tracing, lowering and in backend compiles (which include reads
    of the persistent cache), and the persistent cache's requests, hits
    and writes.  A request that neither hits nor writes compiled faster
    than ``jax_persistent_cache_min_compile_time_secs`` and is not
    cached."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"
    WRITE = "/jax/compilation_cache/cache_misses"  # recorded on write
    READ = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax.monitoring

        self.counts: dict[str, int] = {}
        self.spans: dict[str, list[tuple[float, float]]] = {}
        self.read_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        self.counts[event] = self.counts.get(event, 0) + 1

    def _span(self, event, start, end, **_):
        self.spans.setdefault(event, []).append((start, end))

    def _duration(self, event, secs, **_):
        if event == self.READ:
            self.read_s += secs

    def mark(self) -> tuple:
        return (dict(self.counts), {k: len(v) for k, v in self.spans.items()},
                self.read_s)

    def summary(self, since: tuple = ({}, {}, 0.0)) -> str:
        """One line for what happened after ``since`` (a ``mark()``);
        nested traces overlap, so seconds are the union of the spans."""
        import jax

        def n(event):
            return self.counts.get(event, 0) - since[0].get(event, 0)

        def wall(event):
            spans = sorted(self.spans.get(event, [])[since[1].get(event, 0):])
            total, end = 0.0, float("-inf")
            for a, b in spans:
                total += max(0.0, b - max(a, end))
                end = max(end, b)
            return total, len(spans)

        (tr, n_tr), (lo, n_lo), (co, n_co) = (
            wall(self.TRACE), wall(self.LOWER), wall(self.COMPILE))
        uncached = n(self.REQUEST) - n(self.HIT) - n(self.WRITE)
        return (f"trace {tr:.3f} s ({n_tr} jits), lower {lo:.3f} s "
                f"({n_lo}), backend compile or cache read {co:.3f} s "
                f"({n_co}); persistent cache: {n(self.REQUEST)} requests, "
                f"{n(self.HIT)} hits ({self.read_s - since[2]:.3f} s "
                f"reading), {n(self.WRITE)} written, {uncached} compiled "
                f"but not cached (under "
                f"{jax.config.jax_persistent_cache_min_compile_time_secs} s)")


# ---------------------------------------------------------------------------
# default phase: one chip
# ---------------------------------------------------------------------------


def serve_phase(serve_argv, compiles: CompileLog):
    """Serve the trace through the launcher with the recorder armed;
    returns the ServeRun and the recorder."""
    from repro.launch import serve
    from repro.obs import trace as obs_trace

    rec = obs_trace.start(obs_trace.Recorder(meta={"tool": "chip_smoke"}))
    mark = compiles.mark()
    t0 = time.perf_counter()
    try:
        run = serve.main(serve_argv)
    finally:
        obs_trace.stop()
    wall = time.perf_counter() - t0
    warm = [e for e in rec.events() if e["name"] == "compile.warmup"]
    compile_s = sum(e["dur"] for e in warm) / 1e6
    say(f"serve wall {wall:.3f} s, compile/warmup {compile_s:.3f} s "
        f"({sum(e['args']['programs'] for e in warm)} programs)")
    say(f"serve compiles: {compiles.summary(mark)}")
    return run, rec


class _Tape:
    """Greedy sampler that records its choices, or replays a recording:
    the compared run then feeds the decode step the same tokens as the
    reference run, so one flipped argmax cannot make the inputs differ."""

    def __init__(self, replay=None):
        from repro.serve.sampling import GreedySampler

        self.greedy = GreedySampler()
        self.replay = replay
        self.chosen = []

    def select(self, logits):
        pick = self.greedy.select(logits)
        self.chosen.append(pick)
        return pick if self.replay is None else self.replay[len(self.chosen) - 1]


def swap_decode_rows(name, args):
    """Planted fault: in the decode step, rows 0 and 1 swap block tables
    (each reads, and writes into, the other request's pages)."""
    import jax.numpy as jnp
    import numpy as np

    if name != "decode":
        return args
    table = np.array(args[4])
    table[[0, 1]] = table[[1, 0]]
    return args[:4] + (jnp.asarray(table),) + args[5:]


def drop_decode_page(page_size: int):
    """Planted fault: the decode step's lengths are one page short, so
    each row's attention misses its last ``page_size`` keys (a page-loop
    bound one short; the new token's write is dropped too)."""
    import jax.numpy as jnp
    import numpy as np

    def fault(name, args):
        if name != "decode":
            return args
        lengths = np.maximum(np.asarray(args[5]) - page_size, 0)
        return args[:5] + (jnp.asarray(lengths.astype(np.int32)),)
    return fault


def capture_logits(cfg, params, config, reqs, policy, tape, *, fault=None,
                   f32=False):
    """Run ``reqs`` (max_new=2: prefill + one decode step) through a fresh
    sync engine under ``policy``, choosing tokens through ``tape``;
    return [(step name, host logits)], decode logits cut to the live
    rows (slots 0.. in admission order).  ``fault(name, args)`` rewrites
    the arguments of each model step; ``f32`` runs params and pages in
    float32 with matmul precision highest.  The pool holds just these
    requests."""
    import contextlib
    import dataclasses
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import kernels
    from repro.serve import PagedEngine, Request

    config = dataclasses.replace(
        config, pages=1 + len(reqs) * (config.cache_len // config.page_size))
    if f32:
        params = jax.tree.map(
            lambda a: a.astype(jnp.float32)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    eng = PagedEngine(cfg, params, config=config, sampler=tape)
    if f32:
        eng.caches = jax.tree.map(lambda a: a.astype(jnp.float32), eng.caches)
    got = []
    dispatch = eng._dispatch

    def spy(name, *args):
        if fault is not None:
            args = fault(name, args)
        out = dispatch(name, *args)
        logits = np.asarray(out[0], np.float32)
        if name == "decode":
            logits = logits[: len(reqs)]
        got.append((name, logits))
        return out

    eng._dispatch = spy
    with contextlib.ExitStack() as ctx:
        ctx.enter_context(kernels.use_policy(policy))
        if f32:
            ctx.enter_context(jax.default_matmul_precision("highest"))
        eng.run([Request(rid=r.rid, prompt=list(r.prompt), max_new=2)
                 for r in reqs])
    del eng, spy, dispatch, params
    gc.collect()  # the spy closes a cycle over the engine and its pool
    return got


def captures(page_size: int) -> tuple:
    """The runs logits_phase compares with the bf16 reference run:
    (column, kernel policy, capture_logits options)."""
    return (
        ("pallas", None, {}),
        ("f32", "backend=reference", {"f32": True}),
        ("swap", None, {"fault": swap_decode_rows}),
        ("page", None, {"fault": drop_decode_page(page_size)}),
    )


def logits_phase(run, prefix_len: int) -> None:
    import numpy as np

    eng = run.engine
    # two requests behind the shared prefix (the second hits the cached
    # chain: suffix prefill through pallas_prefill) and one cold request
    by_prefix = {}
    for r in sorted(run.done, key=lambda r: r.rid):
        by_prefix.setdefault(tuple(r.prompt[:prefix_len]), []).append(r)
    groups = sorted(by_prefix.values(), key=len, reverse=True)
    check(len(groups[0]) >= 2, "trace has no two requests sharing a prefix")
    picks = groups[0][:2] + [g[0] for g in groups[1:2]]
    say(f"logits check on requests {[r.rid for r in picks]} "
        f"(prompt lengths {[len(r.prompt) for r in picks]})")
    # the bf16 reference run picks the tokens; every other run replays
    # them, so all runs feed their steps the same inputs
    ref_tape = _Tape()
    ref = capture_logits(eng.cfg, eng.params, eng.config, picks,
                         "backend=reference", ref_tape)
    steps = [n for n, _ in ref]
    runs, tapes = {"ref": ref}, {}
    for col, policy, opts in captures(eng.page_size):
        tapes[col] = _Tape(replay=ref_tape.chosen)
        runs[col] = capture_logits(eng.cfg, eng.params, eng.config, picks,
                                   policy, tapes[col], **opts)
        check([n for n, _ in runs[col]] == steps,
              f"{col} step sequence {[n for n, _ in runs[col]]} differs "
              f"from the reference run's {steps}")
    for col in ("pallas", "f32"):
        for name, a in runs[col]:
            check(bool(np.isfinite(a).all()), f"non-finite {col} {name} logits")
    pairs = (("pallas", "ref"), ("ref", "f32"), ("pallas", "f32"),
             ("swap", "ref"), ("page", "ref"))
    table = {p: [rel_diff(runs[p[0]][i][1], runs[p[1]][i][1])
                 for i in range(len(steps))] for p in pairs}
    heads = [f"{a}-{b}" for a, b in pairs]
    say("logits max rel diff per step, max |a-b| / max |b|:")
    say(f"{'step':<16}" + "".join(f"{h:>12}" for h in heads))
    for i, name in enumerate(steps):
        say(f"{name:<16}" + "".join(f"{table[p][i]:>12.6g}" for p in pairs))
    worst = {p: max(v) for p, v in table.items()}
    say(f"{'max':<16}" + "".join(f"{worst[p]:>12.6g}" for p in pairs))
    agree = sum(int((x[:len(y)] == y).sum())
                for x, y in zip(tapes["pallas"].chosen, ref_tape.chosen))
    total = sum(y.size for y in ref_tape.chosen)
    say(f"logits tolerance {LOGITS_RTOL} (max |pallas-ref| / max |ref|), "
        f"measured max rel diff {worst['pallas', 'ref']:.6g}; planted "
        f"row-swap fault {worst['swap', 'ref']:.6g} (must exceed it), "
        f"dropped-page fault {worst['page', 'ref']:.6g} (reported)")
    say(f"greedy-token agreement {agree}/{total} (reported, not gated)")
    check(worst["pallas", "ref"] <= LOGITS_RTOL,
          f"logits differ from the reference backend: "
          f"{worst['pallas', 'ref']:.6g} > {LOGITS_RTOL}")
    check(worst["swap", "ref"] > LOGITS_RTOL,
          f"the planted row-swap fault reads {worst['swap', 'ref']:.6g}, "
          f"within the tolerance {LOGITS_RTOL}: the logits check cannot "
          f"see it")


def rel_diff(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def kernel_phase(cfg, seed: int) -> None:
    """Each main-path kernel against the reference backend on identical
    inputs at full width, shaped like the engine's calls: paged decode
    (8 rows, 2048-token table, two idle rows of length 0),
    pallas_prefill (a full 64-token chunk, bf16 and int8 pages, and a
    bucket-padded last chunk of 18 tokens at position 384) and the tiled
    linear with its fused bias + silu epilogue.  Rows the engine
    discards (idle slots, bucket padding) are not compared."""
    import jax
    import jax.numpy as jnp

    from repro import kernels
    from repro.nn.attention import packed_heads
    from repro.nn.kvquant import quantize_kv

    kvh, h, d = cfg.attn.n_kv_heads, cfg.attn.n_heads, cfg.attn.head_dim
    b, width, ps, idle = 8, 128, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n_pages = b * width + 1
    # one layer's pool, laid out as the engine's (lane-packed heads) and
    # read as layer 0 of a stack of one
    pack = packed_heads(kvh, d)
    kp, vp = (jax.random.normal(k, (1, kvh // pack, n_pages, ps, pack * d),
                                jnp.bfloat16) for k in ks[:2])
    table = (jax.random.permutation(ks[2], b * width) + 1).reshape(b, width)
    table = table.astype(jnp.int32)
    lengths = jax.random.randint(ks[3], (b,), 65, width * ps + 1, jnp.int32)
    # idle decode slots: length 0, index 0, an all-null table row
    dec_lengths = lengths.at[b - idle:].set(0)
    dec_table = table.at[b - idle:].set(0)
    dec_index = jnp.maximum(dec_lengths - 1, 0)
    q1 = jax.random.normal(ks[4], (b, 1, h, d), jnp.bfloat16)
    q64 = jax.random.normal(ks[5], (1, 64, h, d), jnp.bfloat16)
    # int8 pools keep one head per row
    kp8, vp8 = (a.reshape(1, kvh // pack, n_pages, ps, pack, d)
                .transpose(0, 1, 4, 2, 3, 5).reshape(1, kvh, n_pages, ps, d)
                for a in (kp, vp))
    (kq, kscale), (vq, vscale) = quantize_kv(kp8), quantize_kv(vp8)
    x = jax.random.normal(ks[6], (64, cfg.d_model), jnp.bfloat16)
    w = (0.02 * jax.random.normal(ks[7], (cfg.d_model, cfg.d_ff))
         ).astype(jnp.bfloat16)
    bias = jnp.linspace(-1, 1, cfg.d_ff).astype(jnp.bfloat16)
    def paged(policy, *args):
        return kernels.op("paged_attention")(*args, policy=policy)

    def linear(policy, x, w, bias):
        return kernels.linear(x, w, bias=bias, activation="silu",
                              policy=policy)

    # a suffix's last chunk: 64 bucket rows from position 384, 18 real
    pad_from, pad_to = 384, 402
    pad_start = jnp.array([pad_from], jnp.int32)
    pad_len = jnp.array([pad_to], jnp.int32)
    every = (slice(None),)
    # (fn, args, the output rows the engine keeps)
    cases = {
        "paged decode": (paged, (q1, kp, vp, dec_table, dec_index,
                                 dec_lengths, 0), (slice(0, b - idle),)),
        "pallas_prefill": (paged, (q64, kp, vp, table[:1], lengths[:1] - 64,
                                   lengths[:1], 0), every),
        "pallas_prefill int8": (paged, (q64, kq, vq, table[:1],
                                        lengths[:1] - 64, lengths[:1], 0,
                                        kscale, vscale), every),
        "pallas_prefill padded": (paged, (q64, kp, vp, table[:1], pad_start,
                                          pad_len, 0),
                                  (0, slice(0, pad_to - pad_from))),
        "linear bias+silu": (linear, (x, w, bias), every),
    }
    for name, (fn, args, keep) in cases.items():
        step = jax.jit(fn, static_argnums=0)
        rel = rel_diff(step(None, *args)[keep],
                       step("backend=reference", *args)[keep])
        say(f"kernel {name}: max rel diff {rel:.6g} vs reference "
            f"(tolerance {KERNEL_RTOL})")
        check(rel <= KERNEL_RTOL,
              f"{name} differs from the reference backend: {rel:.6g}")


def default_phase(seed: int, compiles: CompileLog) -> None:
    import jax

    from repro.configs import get_config

    cfg = get_config(ARCH)
    say(f"arch {cfg.name}: d_model {cfg.d_model}, layers {cfg.n_layers}, "
        f"heads {cfg.attn.n_heads}/{cfg.attn.n_kv_heads} x "
        f"{cfg.attn.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
        f"bf16 params from seed {seed}")
    argv = SERVE_ARGV + ["--seed", str(seed)]
    run, rec = serve_phase(argv, compiles)
    sched = check_kernels(rec)
    for op, pick in sorted(sched.items()):
        say(f"schedule {op}: {json.dumps(pick, sort_keys=True)}")
    for op, name in REQUIRED_SCHEDULES:
        check(f"{name}/pallas" in sched.get(op, {}),
              f"{op} never dispatched the {name!r} pallas schedule")
    check_no_fallback()
    snap = run.snapshot
    n = len(run.done)
    check(snap["requests_drained"] == n and n > 0,
          f"drained {snap['requests_drained']} of {n} requests")
    tokens = sum(len(r.out) for r in run.done)
    say(f"requests drained {n}, tokens out {tokens} "
        f"(snapshot tokens_out {snap['tokens_out']}, prefix hit tokens "
        f"{run.engine.prefix.hit_tokens})")
    check(run.engine.prefix.hit_tokens > 0, "no prefix hit on the trace")
    logits_phase(run, int(argv[argv.index("--shared-prefix") + 1]))
    kernel_phase(cfg, seed)
    check_no_fallback()
    say(f"peak_bytes_in_use {peak_bytes(jax.devices()[0])}")


# ---------------------------------------------------------------------------
# --four-chip: the mesh-sharded page pool
# ---------------------------------------------------------------------------


def four_chip_phase(seed: int) -> None:
    import jax

    from repro.launch import serve
    from repro.obs import trace as obs_trace

    check(len(jax.devices()) >= 4, f"--four-chip needs 4 devices, have "
          f"{len(jax.devices())}")
    argv = FOUR_CHIP_ARGV + ["--seed", str(seed)]
    rec = obs_trace.start(obs_trace.Recorder(meta={"tool": "chip_smoke"}))
    try:
        t0 = time.perf_counter()
        one = serve.main(argv)
        expect = {r.rid: r.out for r in one.done}
        say(f"S=1 oracle on {jax.devices()[0]}: {len(expect)} requests, "
            f"{time.perf_counter() - t0:.3f} s")
        del one
        for mode in ("unicast", "sw_tree", "hw"):
            t0 = time.perf_counter()
            run = serve.main(argv + ["--num-shards", "4", "--mesh",
                                     "--mcast-mode", mode])
            for leaf in jax.tree.leaves(run.engine.caches):
                check(len(leaf.sharding.device_set) == 4,
                      f"page array not sharded over 4 devices: "
                      f"{leaf.sharding}")
            got = {r.rid: r.out for r in run.done}
            st = run.engine.stats()
            same = got == expect
            say(f"S=4 {mode}: {len(got)} requests, token streams "
                f"{'identical' if same else 'DIFFER'} to S=1, broadcast "
                f"chains {st['broadcast_chains']} pages "
                f"{st['broadcast_pages']} fabric bytes "
                f"{st['broadcast_fabric_bytes']}, "
                f"{time.perf_counter() - t0:.3f} s")
            check(same, f"S=4 {mode} token streams differ from S=1")
            run.engine.check()
            del run
    finally:
        obs_trace.stop()
    sched = check_kernels(rec)
    for op, pick in sorted(sched.items()):
        say(f"schedule {op}: {json.dumps(pick, sort_keys=True)}")
    check("pallas/pallas" in sched.get("paged_attention", {}),
          "the sharded path never dispatched the pallas decode kernel")
    check_no_fallback()
    for d in jax.devices()[:4]:
        say(f"{d}: peak_bytes_in_use {peak_bytes(d)}")


# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 4-chip sharded page-pool comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro sources under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro import runtime

    cache_dir = runtime.setup_compile_cache()
    import jax

    compiles = CompileLog()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"refusing to run on it", file=sys.stderr)
        return 1
    say(f"device {dev.device_kind} x {len(devs)} (platform {dev.platform}), "
        f"jax {jax.__version__}")
    entries0 = cache_entries(cache_dir)
    say(f"compile cache {cache_dir}: {entries0} entries")
    try:
        if args.four_chip:
            four_chip_phase(args.seed)
        else:
            default_phase(args.seed, compiles)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    entries = cache_entries(cache_dir)
    say(f"compile cache {cache_dir}: {entries} entries "
        f"({entries - entries0:+d}); run compiles: {compiles.summary()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
