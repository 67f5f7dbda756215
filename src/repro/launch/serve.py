"""Serving launcher: batched prefill + decode with continuous batching.

Two KV-cache backends behind one CLI:

* ``--kv paged`` (the serving subsystem, ``repro.serve``): page-pool KV
  cache with radix-tree **prefix sharing** — a prompt prefix prefilled
  once is multicast (refcount bump, zero compute) to every request that
  shares it — plus watermark admission, preemption-by-swap, and the
  ``paged_attention`` kernel op.
* ``--kv dense`` (the fallback, this module's :class:`Server`): one
  right-sized ring-buffer cache slot per batch lane, prefill written
  in place into the slot.

Both paths prefill in **shared length buckets** (one XLA program per
bucket, not one per prompt length; padded positions are masked out of
the cache) and produce identical token streams — CI runs the smoke
workload under both and diffs the output.

``--server`` switches from the fixed request list to the **async
continuous-batching server loop** (`repro.serve.server.ServeLoop`,
paged engine only): a seeded Poisson trace (``--qps``, ``--duration``,
``--seed``, shared-prefix mix via ``--shared-prefix``/``--shared-frac``)
arrives in real time, prefills land between decode ticks, and every
request streams its tokens through the emit queue.  ``--server-driver
sync`` replays the *same* seeded trace through the synchronous
turn-by-turn ``PagedEngine.run`` — the correctness oracle: both drivers
print identical per-request token lines, which CI diffs
(``serve-load-smoke``).  The loop driver validates its flat metrics
snapshot against the schema, asserts every request DRAINED, and writes
the snapshot to ``--metrics-json`` when given.  ``--seed`` threads one
seed through parameter init, the load generator, and any ``--chaos``
fault plan, so a server run — chaos legs included — is exactly
reproducible from its command line.

Every paged-serving knob is one :class:`repro.serve.ServeConfig` field;
the CLI flags are derived from the dataclass (``add_serve_args``), so
``--num-shards 4 --mcast-mode sw_tree [--mesh]`` turns on the
mesh-sharded page pool with multicast page-chain broadcast (``--mesh``
additionally shards the device page arrays over a 1-D mesh — CI forces
4 CPU devices with ``XLA_FLAGS=--xla_force_host_platform_device_count``).

``main(argv)`` also runs in-process and returns a :class:`ServeRun`
(the engine, the drained requests and, under ``--server``, the metrics
snapshot) — ``chip_smoke.py`` drives the chip through it.

CPU demo: PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
    --reduced --requests 6 --max-new 16 [--kv paged]
Server:  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
    --reduced --server --qps 6 --duration 1.0 --max-slots 3 --shared-prefix 24
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels, runtime
from repro.configs import ARCHS, get_config
from repro.configs.registry import draft_for
from repro.models import lm
from repro.serve import (  # noqa: F401 (Request re-export)
    Fault,
    FaultPlan,
    Lifecycle,
    LoadGen,
    PagedEngine,
    Request,
    ServeConfig,
    ServeLoop,
    ServeMetrics,
    add_serve_args,
    pad_to_bucket,
    validate_snapshot,
)
from repro.serve import config as serve_config
from repro.serve.sampling import Sampler, get_sampler


class Server:
    """Continuous-batching decode server, dense ring-buffer KV caches
    (single-host demo scale).  The dense fallback: every arch family
    (local windows, recurrent mixers) — the paged engine covers
    global-attention models."""

    def __init__(self, cfg, params, *, max_batch: int = 4, cache_len: int = 256,
                 prompt_bucket: int = 16, sampler: Sampler | None = None):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.sampler = sampler if sampler is not None else get_sampler("greedy")
        self.caches = lm.init_cache(cfg, max_batch, cache_len)
        self.active: dict[int, Request] = {}  # slot -> request
        self.pos = np.zeros(max_batch, np.int32)
        self.last_tok = np.zeros(max_batch, np.int32)
        # right-pad-to-bucket prefill is exact only when padded tokens
        # cannot influence real ones: global attention (no ring wrap),
        # no recurrent mixer state (which would absorb the pads), and
        # no MoE (expert capacity scales with the padded length, so
        # pads would consume capacity and change real tokens' routing)
        self._bucket = prompt_bucket if all(
            bd.mixer == "attn" and bd.window is None and bd.ff != "moe"
            for bd in cfg.layer_defs
        ) else None

        self._decode = jax.jit(
            lambda p, c, t, i: lm.decode_step(p, cfg, c, t, i)
        )

        def prefill_one(p, t, li, true_len):
            logits, caches = lm.prefill(
                p, cfg, t, cache_slots=cache_len, logit_index=li
            )
            # bucket padding wrote K/V rows past the prompt: mark them
            # empty so they can never be attended to
            return logits, lm.mask_cache_after(caches, true_len)

        self._prefill_one = jax.jit(prefill_one)

    # ------------------------------------------------------------------
    def _admit(self, req: Request) -> bool:
        free = [s for s in range(self.max_batch) if s not in self.active]
        if not free:
            return False
        slot = free[0]
        n = len(req.prompt)
        toks = (pad_to_bucket(req.prompt, self._bucket) if self._bucket
                else np.asarray(req.prompt, np.int32)[None])
        logits, caches_one = self._prefill_one(
            self.params, jnp.asarray(toks), jnp.int32(n - 1), jnp.int32(n)
        )
        # in-place slot write (no whole-cache pad/copy): every ring
        # buffer is already sized to cache_len via prefill(cache_slots=)
        self.caches = jax.tree.map(
            lambda full, one: full.at[:, slot : slot + 1].set(one)
            if full.ndim >= 2 else full,
            self.caches, caches_one,
        )
        self.active[slot] = req
        self.pos[slot] = n
        self.last_tok[slot] = int(self.sampler.select(logits)[0, -1])
        req.out.append(int(self.last_tok[slot]))
        return True

    def run(self, requests: list[Request]) -> list[Request]:
        queue = list(requests)
        done: list[Request] = []
        while queue or self.active:
            while queue and self._admit(queue[0]):
                queue.pop(0)
            if not self.active:
                continue
            toks = jnp.asarray(self.last_tok, jnp.int32)[:, None]
            # ragged continuous batching: every slot decodes at ITS position
            idx = jnp.asarray(self.pos, jnp.int32)
            logits, self.caches = self._decode(self.params, self.caches, toks, idx)
            nxt = self.sampler.select(logits)[:, -1]
            finished = []
            for slot, req in list(self.active.items()):
                self.pos[slot] += 1
                self.last_tok[slot] = nxt[slot]
                req.out.append(int(nxt[slot]))
                if len(req.out) >= req.max_new:
                    finished.append(slot)
            for slot in finished:
                done.append(self.active.pop(slot))
        return done


@dataclasses.dataclass
class ServeRun:
    """What one ``main`` call served: the engine it built, the drained
    requests, and (``--server`` loop driver) the validated metrics
    snapshot."""

    engine: PagedEngine | Server
    done: list[Request]
    snapshot: dict | None = None


def _print_request_lines(done: list[Request]) -> None:
    # stdout is the parity surface: the async loop and the synchronous
    # oracle must print byte-identical lines (CI diffs them)
    for r in sorted(done, key=lambda r: r.rid):
        print(f"req {r.rid}: prompt[{len(r.prompt)}] -> {len(r.out)} "
              f"tokens: {r.out[:8]}...")
    print(f"served {len(done)} requests with continuous batching")


def _length_range(text: str) -> int | tuple[int, int]:
    lo, _, hi = text.partition(",")
    return (int(lo), int(hi)) if hi else int(lo)


def main(argv: list[str] | None = None) -> ServeRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--server", action="store_true",
                    help="async continuous-batching server loop (ServeLoop) "
                         "over a seeded Poisson trace; requires --kv paged")
    ap.add_argument("--qps", type=float, default=4.0,
                    help="--server: mean Poisson arrival rate")
    ap.add_argument("--duration", type=float, default=2.0,
                    help="--server: trace length in seconds")
    ap.add_argument("--prompt-len", type=_length_range, default=(4, 12),
                    metavar="N|LO,HI",
                    help="--server: prompt body length, fixed or drawn "
                         "uniformly from [LO, HI] (the shared prefix comes "
                         "on top)")
    ap.add_argument("--shared-frac", type=float, default=0.5,
                    help="--server: fraction of requests opening with the "
                         "--shared-prefix tokens (multicast fan-out mix)")
    ap.add_argument("--server-driver", choices=("loop", "sync"), default="loop",
                    help="--server: 'loop' runs the async ServeLoop; 'sync' "
                         "replays the identical trace through the turn-by-"
                         "turn PagedEngine.run — the token-parity oracle")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="--server loop: write the validated flat metrics "
                         "snapshot here")
    ap.add_argument("--kv", choices=("dense", "paged"), default=None,
                    help="KV-cache backend: dense ring buffers, or the "
                         "paged pool with prefix sharing (repro.serve); "
                         "default dense, or paged under --server")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common random prefix of this many tokens "
                         "to every request (exercises the paged engine's "
                         "prefix multicast + chunked suffix prefill in the "
                         "CI smoke matrix)")
    ap.add_argument("--kernel-policy", default=None,
                    help='kernel dispatch policy, e.g. "tiled" or '
                         '"backend=reference" (see repro.kernels.api)')
    ap.add_argument("--mesh", action="store_true",
                    help="paged: shard the device page arrays over a "
                         "--num-shards 1-D mesh (requires that many jax "
                         "devices; pairs with --num-shards/--mcast-mode)")
    # every ServeConfig knob becomes a flag, one definition (serve/config.py):
    # --max-slots --cache-len --page-size --pages --kv-dtype --prompt-bucket
    # --prefill-chunk --watermark --queue-cap --kv-guard --kernel-fallback
    # --chaos --seed --num-shards --mesh-axis --mcast-mode --pages-per-shard
    # --trace
    add_serve_args(ap)
    args = ap.parse_args(argv)
    runtime.setup_compile_cache()

    if args.kv is None:
        args.kv = "paged" if args.server else "dense"
    if args.server and args.kv != "paged":
        ap.error("--server requires --kv paged (the ServeLoop is built on "
                 "the paged engine's typed admission/slot machinery)")
    if getattr(args, "draft_model", None) == "auto":
        # resolve the registry pairing here, before ServeConfig validation
        # (the config layer is jax-free and never sees "auto")
        paired = draft_for(args.arch)
        if paired is None:
            ap.error(f"--draft-model auto: registry pairs no draft for "
                     f"--arch {args.arch}")
        args.draft_model = paired
    if getattr(args, "spec_k", 0) and args.kv != "paged":
        ap.error("--spec-k requires --kv paged (speculative verify-accept "
                 "runs on the paged engine's COW page machinery)")
    if args.kernel_policy:
        kernels.set_policy(args.kernel_policy)
    serve_cfg = serve_config.from_args(
        args,
        max_slots=(args.max_slots or args.max_batch) if args.server
        else args.max_batch,
    )
    cfg = get_config(args.arch, reduced=args.reduced)
    rec = _arm_trace(serve_cfg)
    try:
        return _drive(args, cfg, serve_cfg)
    finally:
        # trace lands even on a SystemExit from undrained requests —
        # the failing run is exactly the one worth profiling
        if rec is not None:
            _finish_trace(rec, serve_cfg.trace)


def _drive(args, cfg, serve_cfg: ServeConfig) -> ServeRun:
    params = lm.init(cfg, jax.random.PRNGKey(serve_cfg.seed))
    sampler = get_sampler(serve_cfg.sampler)
    if args.kv == "paged":
        mesh = None
        if args.mesh:
            from repro.launch.mesh import make_serve_mesh

            mesh = make_serve_mesh(serve_cfg.num_shards,
                                   axis=serve_cfg.mesh_axis)
        draft = None
        if serve_cfg.draft_model and serve_cfg.draft_model != "ngram":
            # model draft: second (small) param set through the same
            # KernelOp dispatch; init shares the run seed so the whole
            # spec configuration replays from the command line
            dcfg = get_config(serve_cfg.draft_model, reduced=args.reduced)
            dparams = lm.init(dcfg, jax.random.PRNGKey(serve_cfg.seed))
            draft = (dcfg, dparams)
        server = PagedEngine(cfg, params, config=serve_cfg, mesh=mesh,
                             draft=draft, sampler=sampler)
    else:
        server = Server(cfg, params, max_batch=serve_cfg.max_slots,
                        sampler=sampler)

    plan = serve_cfg.fault_plan()

    if args.server:
        return _run_server(args, cfg, serve_cfg, server, plan)

    rng = np.random.default_rng(serve_cfg.seed)
    prefix = list(rng.integers(0, cfg.vocab, size=args.shared_prefix))
    reqs = [
        Request(rid=i,
                prompt=prefix + list(
                    rng.integers(0, cfg.vocab, size=rng.integers(4, 12))
                ),
                max_new=args.max_new)
        for i in range(args.requests)
    ]
    if plan is not None:
        with plan:
            done = server.run(reqs)
    else:
        done = server.run(reqs)
    # stdout is the parity surface: CI diffs dense vs. paged output, so
    # only mode-independent lines go here (mode details -> stderr)
    _print_request_lines(done)
    if args.kv == "paged":
        print(f"# paged kv stats: {server.stats()}", file=sys.stderr)
    return ServeRun(server, done)


def _run_server(args, cfg, serve_cfg: ServeConfig, engine: PagedEngine,
                plan: FaultPlan | None) -> ServeRun:
    """``--server``: one seeded trace, two drivers.  ``loop`` is the
    async ServeLoop (metrics snapshot validated + optionally written);
    ``sync`` is the turn-by-turn oracle.  Identical stdout by design."""
    gen = LoadGen(
        seed=serve_cfg.seed, qps=args.qps, duration=args.duration,
        vocab=cfg.vocab, prompt_len=args.prompt_len, max_new=args.max_new,
        shared_prefix_len=args.shared_prefix, shared_frac=args.shared_frac,
    )
    trace = gen.trace()
    print(f"# trace: {len(trace)} requests over {args.duration}s @ qps "
          f"{args.qps} (seed {serve_cfg.seed}, driver {args.server_driver})",
          file=sys.stderr)

    if args.server_driver == "sync":
        reqs = [Request(rid=a.rid, prompt=list(a.prompt), max_new=a.max_new)
                for a in trace]
        if plan is not None:
            with plan:
                done = engine.run(reqs)
        else:
            done = engine.run(reqs)
        _print_request_lines(done)
        print(f"# paged kv stats: {engine.stats()}", file=sys.stderr)
        return ServeRun(engine, done)

    loop = ServeLoop(engine, config=serve_cfg, metrics=ServeMetrics())
    if plan is not None:
        with plan:
            results = loop.run_trace(trace)
    else:
        results = loop.run_trace(trace)
    snap = validate_snapshot(loop.snapshot())
    drained = [r.engine_req for r in results.values()
               if r.state is Lifecycle.DRAINED]
    _print_request_lines(drained)
    print(f"# serve metrics: {json.dumps(snap, sort_keys=True)}",
          file=sys.stderr)
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.metrics_json}", file=sys.stderr)
    if plan is None:
        # without injected faults every request must drain; a chaos run
        # may legitimately end with typed failures (reported above)
        bad = {r.rid: r.state.name for r in results.values()
               if r.state is not Lifecycle.DRAINED}
        if bad:
            raise SystemExit(f"requests did not drain: {bad}")
    return ServeRun(engine, drained, snap)


def _arm_trace(serve_cfg: ServeConfig):
    """Arm the global obs recorder when ``--trace PATH`` was given.

    Armed *before* the engine is built so jit-trace-time kernel
    dispatch spans (``dispatch.*``) land in the trace too."""
    if not serve_cfg.trace:
        return None
    from repro.obs import trace as obs_trace

    # default Recorder clock is time.monotonic — the same clock
    # ServeLoop/metrics read, so span endpoints share their timebase
    rec = obs_trace.Recorder(meta={
        "tool": "launch.serve",
        "seed": serve_cfg.seed,
        "num_shards": serve_cfg.num_shards,
        "mcast_mode": serve_cfg.mcast_mode,
    })
    obs_trace.start(rec)
    return rec


def _finish_trace(rec, path: str) -> None:
    """Disarm, export the trace, and write the schema-validated
    efficiency report next to it (``PATH.report.json``).  Status lines
    go to stderr only — stdout is the CI token-parity surface."""
    from repro.obs import analyze as obs_analyze
    from repro.obs import export as obs_export
    from repro.obs import trace as obs_trace

    obs_trace.stop()
    obs_export.write(rec, path)
    report = obs_analyze.analyze(obs_export.validate_trace(obs_export.to_chrome(rec)))
    obs_analyze.validate_report(report)
    report_path = path + ".report.json"
    with open(report_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote trace {path} ({len(rec)} events, "
          f"{rec.n_dropped} dropped) + report {report_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
