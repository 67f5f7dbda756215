"""The benchmark harness: one run of one cell.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by its name in ``BENCHMARK.json``:

* ``configs/<config>.json``: the sizes as run (``model_type`` picks
  ``configs/<model_type>_program.py``, which builds the system under
  test from them, and ``configs/<model_type>_reference.py``, the plain
  reference and the weights);
* ``traffic/<mix>.json``: the mix, read by ``traffic.py``;
* ``metrics/<metric>.py``: the reader of one per-layer metric, with
  ``read(run) -> float | None`` (``decode_step_ms.batch``, the same
  quantity moving another end-to-end metric, is read by
  ``metrics/decode_step_ms.py``);
* ``limits/<workload>.json``: the limit of each number the correctness
  comparison holds against.

A run builds the engine and its serving loop in-process with weights
made on the device from the seed, warms the programs the cell's traffic
uses, drives the traffic through ``ServeLoop.submit`` for the window,
checks what the window served against the float32 reference, and
prints one JSON line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import queue
import shutil
import sys
import tempfile
import time
from pathlib import Path

import check
import devtrace
import traffic
from stats import percentile

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the profiler's host tracer: jit dispatch and annotations, no Python calls
HOST_TRACER_LEVEL = 1
WARM_RID = 10**9


class Refused(Exception):
    """The run cannot measure here (no chip, unknown device)."""


def say(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file, with its "name"
    mix: dict  # the traffic file
    end_to_end: list[dict]  # BENCHMARK.json entries reported by this cell
    per_layer: list[dict]
    limits: dict
    bench: Path

    def module(self, kind: str, name: str):
        return load_module(self.bench / kind / f"{name}.py")

    def reader(self, metric: str):
        """A per-layer metric's reader: ``metrics/<metric>.py`` or, for a
        quantity split by the end-to-end metric it moves
        (``decode_step_ms.batch``), the reader of its name before the
        first dot."""
        path = self.bench / "metrics" / f"{metric}.py"
        if not path.is_file():
            path = self.bench / "metrics" / f"{metric.split('.')[0]}.py"
        return load_module(path)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _for(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def resolve(spec: dict, workload: str, bench: Path = BENCH,
            root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    config["name"] = conf["name"]
    mix = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((bench / "limits" / f"{workload}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _for(m, workload)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _for(m, workload) and m["moves"] in moved]
    return Cell(workload, int(w["chips"]), config, mix, e2e, per_layer,
                limits, bench)


# ---------------------------------------------------------------------------
# what a per-layer metric reads
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunData:
    """Everything one run recorded, handed to each metric's reader.
    Times are the serving loop's clock (``time.monotonic``) seconds."""

    cfg: dict
    serve: dict
    peak: dict  # this device's row of peaks.json
    chips: int
    t0: float  # the window
    t1: float
    arrivals: dict  # rid -> traffic.Arrival
    reqs: dict  # rid -> recorder.Req
    ticks: list  # (end time, live slots)
    spans: list  # (name, start, end, args) from the program's own spans
    instants: list  # (name, t, args)
    stats: dict  # engine counters over the window (stats_delta)
    trace: devtrace.Trace | None  # the profiler's trace of the window
    trace_window: tuple | None  # the window on the profiler's clock

    def device_ops(self) -> list:
        """Every device's operations, together (a single chip here)."""
        return [e for ops in self.trace.device.values() for e in ops]

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1


def _obs_events(rec) -> tuple[list, list]:
    spans, instants = [], []
    for e in rec.events():
        t = rec.t0 + e["ts"] * 1e-6
        if e.get("ph") == "X":
            spans.append((e["name"], t, t + e["dur"] * 1e-6,
                          e.get("args", {})))
        elif e.get("ph") == "i":
            instants.append((e["name"], t, e.get("args", {})))
    return spans, instants


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


class CompileCounter:
    """What this process compiled, from ``jax.monitoring``: the end time
    of every backend compile (or persistent-cache read), so that a
    compile inside the window shows as a fault of the warm-up, and the
    persistent cache's requests, hits and writes."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"
    WRITE = "/jax/compilation_cache/cache_misses"  # recorded on a write

    def __init__(self):
        import jax.monitoring

        self.ends: list[float] = []
        self.counts: dict[str, int] = {}
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._count)

    def _on(self, event, secs, **_):
        if event == self.COMPILE:
            self.ends.append(time.monotonic())
            self.compile_s += secs

    def _count(self, event, **_):
        self.counts[event] = self.counts.get(event, 0) + 1

    def between(self, a: float, b: float) -> int:
        return sum(a <= t <= b for t in self.ends)

    def summary(self) -> str:
        n = self.counts.get
        return (f"{len(self.ends)} backend compiles or cache reads "
                f"({self.compile_s:.3f} s); persistent cache: "
                f"{n(self.REQUEST, 0)} requests, {n(self.HIT, 0)} hits, "
                f"{n(self.WRITE, 0)} written")


def check_devices(chips: int, peaks: dict, *, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if require_tpu and dev.device_kind not in peaks:
        raise Refused(f"device kind {dev.device_kind!r} is not in the "
                      f"peaks table ({sorted(peaks)})")
    return devs[:chips]


def warm_up(loop, arrivals, vocab: int, seed: int) -> int:
    """Compile every program the cell's traffic uses, and only those:
    the cold-prefill bucket of each prompt that can arrive cold, the
    suffix-prefill buckets of the prompts that can hit the prefix cache,
    and the decode step.  Then one cold request, and a prefix hit where
    the traffic has them, run through the loop for the small programs
    around those (the sampler, the transfers).  Returns the number of
    programs warmed."""
    import numpy as np

    from repro.serve.engine import bucket_len

    eng = loop.engine
    seen, cold, hits = set(), set(), []
    for a in arrivals:
        if a.doc is None or a.doc not in seen:
            cold.add(len(a.prompt))
        else:
            hits.append(a)
        seen.add(a.doc)
    suffix = set()
    chunk = eng.prefill_chunk
    for a in hits:
        for n_shared in range(0, len(a.prompt), eng.page_size):
            rest = len(a.prompt) - n_shared
            suffix.update({min(chunk, rest), rest % chunk} - {0} if chunk
                          else {rest})
    n = loop.warmup(sorted(cold), suffix_lens=sorted(suffix))
    # the requests run at the shortest cold length (a bucket warmed
    # above); the second shares the first's whole pages, as a hit does
    rng = np.random.default_rng([seed % 2**63, 7])
    base = [int(x) for x in rng.integers(0, vocab, min(cold))]
    reqs = [base]
    if hits:
        shared = len(base) // eng.page_size * eng.page_size
        reqs.append(base[:shared] + [int(x) for x in rng.integers(0, vocab, 4)])
    for i, prompt in enumerate(reqs):
        loop.submit(prompt, 3, rid=WARM_RID + i).result(timeout=600)
    say(f"warm-up: {n} programs (cold buckets "
        f"{sorted({bucket_len(x, eng.prompt_bucket) for x in cold})}, suffix "
        f"buckets {sorted({bucket_len(x, eng.prompt_bucket) for x in suffix})})"
        f" and {len(reqs)} requests")
    return n


def drive_open(loop, arrivals, t0: float, seconds: float) -> tuple[list, list]:
    """Submit each request at its due time; returns the handles and how
    late each submission was (seconds)."""
    handles, late = [], []
    for a in arrivals:
        due = t0 + a.t
        delay = due - loop.clock()
        if delay > 0:
            time.sleep(delay)
        late.append(loop.clock() - due)
        handles.append(loop.submit(a.prompt, a.max_new, rid=a.rid,
                                   arrival_t=due))
    return handles, late


def fill_closed(loop, rec, arrivals, deadline_s: float = 600.0):
    """Send each client's first request and wait until every slot holds
    one.  The window opens on a full batch, as a batch job runs once it
    has filled: the fill's admissions race the first decode steps for
    the serving loop's lock, so whether they run back to back or between
    steps would change the number of delayed steps in the window from
    run to run.  Returns each client's remaining queue and the handles
    sent."""
    queues: dict[int, list] = {}
    for a in arrivals:
        queues.setdefault(a.client, []).append(a)
    t = loop.clock()
    handles = []
    for client in sorted(queues):
        a = queues[client].pop(0)
        handles.append(loop.submit(a.prompt, a.max_new, rid=a.rid,
                                   arrival_t=t))
    want = min(loop.max_slots, len(handles))
    while sum(r.admitted is not None for r in rec.copy()[0].values()) < want:
        if loop.clock() - t > deadline_s:
            raise RuntimeError(f"the batch did not fill {want} slots in "
                               f"{deadline_s} s")
        time.sleep(0.01)
    return queues, handles


def drive_closed(loop, rec, arrivals, queues: dict, handles: list,
                 t1: float) -> list:
    """Each client sends its next request when its previous one ends,
    until the window closes."""
    owner = {a.rid: a.client for a in arrivals}

    def send(client: int, due: float) -> None:
        if queues[client]:
            a = queues[client].pop(0)
            handles.append(loop.submit(a.prompt, a.max_new, rid=a.rid,
                                       arrival_t=due))

    while True:
        left = t1 - loop.clock()
        if left <= 0:
            return handles
        try:
            rid = rec.ended.get(timeout=left)
        except queue.Empty:
            return handles
        if rid in owner and loop.clock() < t1:
            send(owner[rid], loop.clock())


def run(cell: Cell, seed: int, seconds: float, traced: bool, *,
        t_start: float, require_tpu: bool = True, fault=None,
        control: bool = False, keep: dict | None = None) -> dict:
    """One run of ``cell``; returns the result line's object.  ``fault``
    is called with the engine before the window, to break the timed
    path underneath; ``control`` adds the float8 control's reading of
    the same sample under ``"control"`` (both for ``control.py`` and the
    tests, never in the benchmark's own runs); ``keep`` receives the
    run's records under ``"data"``."""
    import jax

    from repro import kernels
    from repro.obs import trace as obs_trace
    from repro.serve.engine import PagedEngine
    from repro.serve.server import ServeLoop

    from recorder import Recorder

    peaks = json.loads((cell.bench / "peaks.json").read_text())["devices"]
    devs = check_devices(cell.chips, peaks, require_tpu=require_tpu)
    dev = devs[0]
    say(f"device {dev.device_kind} x {len(jax.devices())} "
        f"(platform {dev.platform}), jax {jax.__version__}")
    compiles = CompileCounter()
    marks = [("import", time.perf_counter())]
    cfg = cell.config
    arch = cell.module("configs", f"{cfg['model_type']}_program")
    ref = cell.module("configs", f"{cfg['model_type']}_reference")
    mcfg, scfg = arch.model_config(cfg), arch.serve_config(cfg)

    params = ref.make_params(cfg, seed)
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    engine = PagedEngine(mcfg, params, config=scfg)
    rec = Recorder()
    loop = ServeLoop(engine, config=scfg, metrics=rec)
    arrivals = traffic.generate(cell.mix, seed=seed, seconds=seconds,
                                vocab=int(cfg["vocab_size"]),
                                cache_len=scfg.cache_len)
    by_rid = {a.rid: a for a in arrivals}
    closed = cell.mix["arrivals"]["process"] == "closed"
    marks.append(("engine", time.perf_counter()))
    n_warm = warm_up(loop, arrivals, int(cfg["vocab_size"]), seed)
    marks.append(("warm-up", time.perf_counter()))
    if fault is not None:
        fault(engine)
    rec.reset()
    kernels.reset_fallback_stats()
    if closed:
        queues, handles = fill_closed(loop, rec, arrivals)
        marks.append(("batch fill", time.perf_counter()))
    engine.stats_delta()

    obs = None
    log_dir = None
    if traced:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = HOST_TRACER_LEVEL
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        obs = obs_trace.start(obs_trace.Recorder(meta={"tool": "bench"}))
    t0 = loop.clock()
    setup_s = time.perf_counter() - t_start
    marks.append(("window start", t_start + setup_s))
    steps = ", ".join(f"{name} {b - a:.3f} s" for (_, a), (name, b)
                      in zip([("start", t_start)] + marks, marks))
    say(f"set-up {setup_s:.3f} s: {steps}; {n_warm} programs warmed; "
        f"{compiles.summary()}")
    t1 = t0 + seconds
    late: list = []
    with jax.profiler.TraceAnnotation("bench.window"):
        if closed:
            handles = drive_closed(loop, rec, arrivals, queues, handles, t1)
        else:
            handles, late = drive_open(loop, arrivals, t0, seconds)
        left = t1 - loop.clock()
        if left > 0:
            time.sleep(left)
    t_end = loop.clock()
    stats = engine.stats_delta()
    with loop._mu:  # the pages in use, beside the memory peak
        pool = engine.pool
        say(f"page pool at the close: {pool.in_use} of {pool.usable_pages} "
            f"pages in use, {len(engine.prefix.pages())} of them held by "
            f"the prefix cache; peak in use {pool.stats.peak_in_use}")
    if traced:
        obs_trace.stop()
        jax.profiler.stop_trace()
    n_compiles = compiles.between(t0, t_end)
    # requests due in the window are waited for, a minute past its close
    # at most; a closed loop's requests still in flight are cut off
    if closed:
        loop.close(drain=False)
    else:
        deadline = t_end + 60.0
        for h in handles:
            h.stream.closed.wait(max(0.0, deadline - loop.clock()))
        loop.close(drain=False)
    fb = kernels.fallback_stats()
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
    reqs, ticks = rec.copy()
    for h in handles:
        if h.rid in reqs:
            reqs[h.rid].out = h.tokens
    # free the program's state before the reference runs
    del engine, loop, params, handles
    gc.collect()

    spans, instants = _obs_events(obs) if obs is not None else ([], [])
    trace = trace_window = None
    if traced:
        trace = devtrace.load(devtrace.find(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        trace_window = _trace_window(trace)
    data = RunData(cfg=cfg, serve=dataclasses.asdict(scfg),
                   peak=peaks.get(dev.device_kind, {}), chips=cell.chips,
                   t0=t0, t1=t1, arrivals=by_rid, reqs=reqs, ticks=ticks,
                   spans=spans, instants=instants, stats=stats, trace=trace,
                   trace_window=trace_window)

    if keep is not None:
        keep["data"] = data
    attempted, failed, unanswered = _count(data, closed, t_end)
    say(f"window {seconds} s from {t0:.3f}: {attempted} requests attempted, "
        f"{failed} failed, {unanswered} unanswered; "
        f"{sum(len(r.tokens) for r in reqs.values())} tokens served; "
        f"compiles in the window {n_compiles}")
    if late:
        say(f"generator lateness: p50 {percentile(late, 50) * 1e3:.3f} ms, "
            f"max {max(late) * 1e3:.3f} ms over {len(late)} submissions")

    e2e = end_to_end(data, closed, setup_s)
    for name, (value, n) in e2e.items():
        say(f"{name} = {value} over {n} samples")
    if traced:
        metrics = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        for name, v in metrics.items():
            say(f"{name} = {v['value']} {v['unit']}")
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]][0], "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None
                   and e2e[m["name"]][0] is not None}

    checks = check.compare(ref, cfg, seed, data, cell.limits,
                           cache_len=scfg.cache_len)
    checks["unanswered"] = {"value": unanswered, "limit": 0}
    checks["kernel_fallbacks"] = {"value": fb.fallbacks + fb.substitutions,
                                  "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    ctl = None
    if control:
        ctl = check.compare(ref, cfg, seed, data, cell.limits,
                            cache_len=scfg.cache_len, control=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_mem}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if traced:
        ops = data.device_ops()
        w0, w1 = trace_window
        device["busy_s"] = sum(
            devtrace.busy_seconds([e for e in o if w0 <= e.start <= w1])
            for o in trace.device.values()) / max(1, len(trace.device))
        device["window_s"] = w1 - w0
        out["breakdown"] = {
            "device_ops": devtrace.top_ops(ops),
            "idle_gaps": devtrace.idle_gaps(ops, trace.host, w0, w1),
        }
    if ctl is not None:
        out["control"] = ctl
    out["checks"] = checks
    return out


def _trace_window(trace: devtrace.Trace) -> tuple[float, float]:
    """The measured window on the profiler's clock: the benchmark's own
    ``bench.window`` annotation."""
    for h in trace.host:
        if h.name == "bench.window":
            return h.start, h.end
    raise RuntimeError("the trace holds no bench.window annotation")


def _count(data: RunData, closed: bool, t_close: float) -> tuple[int, int, int]:
    """(attempted, failed, unanswered): requests due in the window (in a
    closed loop, the batch's fill too), those refused or failed, and
    those never answered (open loop).  A closed loop's requests cut off
    when the window closed are neither."""
    due = [r for r in data.reqs.values()
           if (r.due <= data.t1 if closed else data.in_window(r.due))]
    failed = sum(1 for r in due if r.state in ("REJECTED", "FAILED")
                 and not (closed and r.ended is not None
                          and r.ended >= t_close))
    unanswered = 0 if closed else sum(1 for r in due if r.state != "DRAINED")
    return len(due), failed, unanswered


def end_to_end(data: RunData, closed: bool, setup_s: float) -> dict:
    """{metric: (value, samples)} over every sample of the window."""
    due = [r for r in data.reqs.values() if data.in_window(r.due)]
    ttft = [r.tokens[0] - r.due for r in due if r.tokens]
    gaps = [b - a for r in data.reqs.values()
            for a, b in zip(r.tokens, r.tokens[1:]) if data.in_window(b)]
    n_tok = sum(1 for r in data.reqs.values() for t in r.tokens
                if data.in_window(t))
    out = {"setup_s": (setup_s, 1),
           "itl_p95_ms": (_ms(percentile(gaps, 95)), len(gaps)),
           "tok_s": (n_tok / (data.t1 - data.t0), n_tok)}
    if not closed:
        out["ttft_p50_ms"] = (_ms(percentile(ttft, 50)), len(ttft))
        out["ttft_p90_ms"] = (_ms(percentile(ttft, 90)), len(ttft))
    else:
        # queue-bound by design in a closed loop: printed, not a metric
        say(f"closed-loop ttft p50 {_ms(percentile(ttft, 50))} ms, p90 "
            f"{_ms(percentile(ttft, 90))} ms over {len(ttft)} requests")
    return out


def _ms(x):
    return None if x is None else x * 1e3


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def setup() -> None:
    """Put the system under test on the path and the compile cache in the
    checkout, before anything imports JAX."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"no system under test at {src}")
    sys.path.insert(0, str(src))
    # the compile cache lives in the checkout, at a fixed path: the
    # program takes the directory this variable names (JAX writes into
    # it only if it exists)
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax

    from repro import runtime

    runtime.setup_compile_cache()
    # cache every program, the ones that compile in under a second too:
    # each run is a new process and pays every compile it cannot read
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = resolve(spec, args.workload)
    try:
        setup()
        out = run(cell, args.seed, args.seconds, bool(args.trace),
                  t_start=t_start)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
