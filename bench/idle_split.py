"""Where a cell's idle device time goes, by what the program was doing:
one traced run (as ``run.py --trace 1``), whose result line is printed
last as usual, and before it one JSON line that splits the window.

    python3 bench/idle_split.py --workload qwen05b.docqa --seed N \\
        --seconds 51

``idle``: the window's idle device seconds, split three ways, in order:

* ``spans``: under a program span on the profiler's host plane, by the
  span's name: the innermost span of the serving loop's lock holder
  (a decode tick's or an admission's, which the lock keeps apart), or
  the decode worker's ``decode.wait`` where no other span runs;
* ``no_request``: no request in the system (none due and unfinished,
  from the benchmark's recorder);
* ``unattributed``: the rest, also by the program span that ended last
  before it (``unattributed_after``), which says where it lies.

``queue``: the window's admissions' queue wait (due time to admission,
the recorder's) split into ``admit_wait`` (the program's
``request.admit_wait``: a slot was free and the request was the queue
head) and the time behind earlier requests; medians and means.

``decode_wait``: the ``decode.wait`` spans that ended in the window,
with and without an admission inside.
"""
import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402
from stats import percentile  # noqa: E402

PREFIXES = ("decode.", "engine.", "request.", "compile.")
WAIT = "decode.wait"


def _clock_offset(run) -> float:
    """Profiler clock minus the program's clock, from the decode ticks
    recorded on both."""
    ours = sorted(s for n, s, _, _ in run.spans if n == "decode.tick")
    theirs = sorted(h.start for h in run.trace.host if h.name == "decode.tick")
    if not ours or len(ours) != len(theirs):
        raise RuntimeError(f"{len(ours)} decode ticks recorded, "
                           f"{len(theirs)} on the profiler's host plane")
    return statistics.median(b - a for a, b in zip(ours, theirs))


def _idle(ops, w0: float, w1: float) -> list:
    gaps, end = [], w0
    for e in sorted(ops, key=lambda e: e.start):
        if e.start > end:
            gaps.append((end, min(e.start, w1)))
        end = max(end, e.end)
        if end >= w1:
            break
    if end < w1:
        gaps.append((end, w1))
    return [(a, b) for a, b in gaps if b > a]


def _busy_requests(run, offset: float) -> list:
    """(start, end) on the profiler's clock while some request was due
    and unfinished."""
    return [(r.due + offset,
             (r.ended if r.ended is not None else float("inf")) + offset)
            for r in run.reqs.values()]


def idle_split(run) -> dict:
    w0, w1 = run.trace_window
    offset = _clock_offset(run)
    ops = [e for e in run.device_ops() if w0 <= e.start <= w1]
    # a sweep over every boundary: idle gaps, program spans, requests
    marks = []
    for a, b in _idle(ops, w0, w1):
        marks += [(a, 1, "idle"), (b, -1, "idle")]
    for h in run.trace.host:
        if h.name.startswith(PREFIXES) and h.end > w0 and h.start < w1:
            marks += [(h.start, 1, h), (h.end, -1, h)]
    for a, b in _busy_requests(run, offset):
        if b > w0 and a < w1:
            marks += [(a, 1, "request"), (b, -1, "request")]
    marks.sort(key=lambda m: (m[0], -m[1]))
    idle = requests = 0
    spans: set = set()
    split: dict = {}
    after: dict = {}
    last = "none"  # the span that ended last
    t_prev = w0
    for t, step, what in marks:
        a, b = max(t_prev, w0), min(t, w1)
        if b > a and idle:
            inner = [h for h in spans if h.name != WAIT]
            waits = [h for h in spans if h.name == WAIT]
            if inner or waits:
                key = max(inner or waits, key=lambda h: h.start).name
            elif not requests:
                key = "no_request"
            else:
                key = "unattributed"
                after[last] = after.get(last, 0.0) + (b - a)
            split[key] = split.get(key, 0.0) + (b - a)
        t_prev = t
        if what == "idle":
            idle += step
        elif what == "request":
            requests += step
        elif step > 0:
            spans.add(what)
        else:
            spans.discard(what)
            last = what.name
    total = sum(split.values())
    named = {k: v for k, v in split.items()
             if k not in ("no_request", "unattributed")}
    return {
        "window_s": w1 - w0, "idle_s": total,
        "spans": dict(sorted(named.items(), key=lambda kv: -kv[1])),
        "no_request": split.get("no_request", 0.0),
        "unattributed": split.get("unattributed", 0.0),
        "unattributed_share": split.get("unattributed", 0.0) / total
        if total else None,
        "unattributed_after": dict(sorted(after.items(),
                                          key=lambda kv: -kv[1])),
        "clock_offset_s": offset,
    }


def queue_split(run) -> dict:
    admit = {a["rid"]: e - s for n, s, e, a in run.spans
             if n == "request.admit_wait"}
    rows = [(r.admitted - r.due, admit[rid])
            for rid, r in run.reqs.items()
            if run.in_window(r.due) and r.admitted is not None
            and rid in admit]
    if not rows:
        return {}
    out = {"n": len(rows)}
    for name, xs in (("queue_wait", [q for q, _ in rows]),
                     ("admit_wait", [a for _, a in rows]),
                     ("behind", [q - a for q, a in rows])):
        out[f"{name}_p50_ms"] = percentile(xs, 50) * 1e3
        out[f"{name}_mean_ms"] = statistics.fmean(xs) * 1e3
    return out


def decode_wait_split(run) -> dict:
    out = {}
    waits = [(e - s, a.get("admitted") or []) for n, s, e, a in run.spans
             if n == WAIT and run.in_window(e)]
    for name, keep in (("with_admission", True), ("without", False)):
        xs = [d for d, rids in waits if bool(rids) == keep]
        out[name] = {"n": len(xs), "mean_ms": statistics.fmean(xs) * 1e3
                     if xs else None,
                     "p95_ms": percentile(xs, 95) * 1e3 if xs else None}
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.resolve(spec, args.workload)
    harness.setup()
    keep: dict = {}
    try:
        out = harness.run(cell, args.seed, args.seconds, True,
                          t_start=T_START, keep=keep)
    except harness.Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    run = keep["data"]
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "idle": idle_split(run), "queue": queue_split(run),
                      "decode_wait": decode_wait_split(run)}))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
