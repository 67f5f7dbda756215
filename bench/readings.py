"""Shared readings of a run for the per-layer metrics: the admissions and
decode steps of the window, from the program's own spans and counters
(armed in the traced run) and the benchmark's recorder."""
from __future__ import annotations


def admissions(run) -> list[tuple[int, float, float, int, int]]:
    """(rid, start, end, hit tokens, prompt tokens) of every admission
    whose prefill began in the window, from the ``request.prefill`` spans
    and the ``prefix.match`` counted inside the admission."""
    admits = [(s, e, a["rid"]) for n, s, e, a in run.spans
              if n == "engine.admit" and a.get("ok")]
    matches = sorted((t, a["hit_tokens"], a["hit_tokens"] + a["miss_tokens"])
                     for n, t, a in run.instants if n == "prefix.match")
    hit_of = {}
    for s, e, rid in admits:
        inside = [m for m in matches if s <= m[0] <= e]
        if inside:
            hit_of[rid] = inside[-1][1:]
    out = []
    for n, s, e, a in run.spans:
        if n == "request.prefill" and run.in_window(s) and a["rid"] in hit_of:
            hit, total = hit_of[a["rid"]]
            out.append((a["rid"], s, e, hit, total))
    return out


def suffix_chunks(run, hit: int, total: int) -> list[tuple[int, int]]:
    """(start, query tokens) of each suffix-prefill call of a prefix hit,
    as the serving configuration chunks it."""
    chunk = run.serve.get("prefill_chunk") or (total - hit)
    return [(hit + c0, min(chunk, total - hit - c0))
            for c0 in range(0, total - hit, chunk)]


def decode_ticks(run) -> list[list[int]]:
    """For each decode step that ended in the window, the context length
    of every row it decoded (the token's own position included)."""
    by_t: dict[float, list[int]] = {}
    for rid, r in run.reqs.items():
        prompt = len(run.arrivals[rid].prompt)
        for j, t in enumerate(r.tokens):
            if j and run.in_window(t):
                by_t.setdefault(t, []).append(prompt + j)
    return [by_t[t] for t in sorted(by_t)]


def spans_in_window(run, name: str) -> list[tuple[float, float]]:
    return [(s, e) for n, s, e, _ in run.spans
            if n == name and run.in_window(e)]


def kernel_seconds(run, pattern: str) -> float:
    """Device time of a kernel's operations (``devtrace.kernel_events``)
    in the traced window."""
    import devtrace

    w0, w1 = run.trace_window
    ops = [e for e in run.device_ops() if w0 <= e.start <= w1]
    return sum(e.dur for e in devtrace.kernel_events(ops, pattern))
