"""Tracing/profiling layer tests: recorder semantics (ring buffer,
thread metadata, zero-cost disabled path), export schema validation,
span nesting against the engine/loop worker structure, and the offline
analyzer's exact cross-checks against the live engine/pool/prefix
counters and ``dist/mcast.bytes_model``."""
import glob
import json
import os
import tracemalloc

import jax
import pytest

from repro.configs import get_config
from repro.dist import mcast
from repro.models import lm
from repro.obs import analyze as obs_analyze
from repro.obs import export as obs_export
from repro.obs import trace as obs_trace
from repro.serve import (
    Lifecycle,
    LoadGen,
    PagedEngine,
    Request,
    ServeConfig,
    ServeLoop,
)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small():
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = lm.init(cfg, KEY)
    return cfg, params


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    assert obs_trace.active() is None, "a test leaked an armed recorder"
    yield
    obs_trace.stop()  # idempotent; keeps one failure from cascading


def _mk_requests(cfg, *, shared_prefix=0, n=4, max_new=5, seed=7):
    import numpy as np

    rng = np.random.default_rng(seed)
    prefix = list(rng.integers(0, cfg.vocab, size=shared_prefix))
    return [
        Request(rid=i,
                prompt=prefix + list(rng.integers(0, cfg.vocab, size=3 + i)),
                max_new=max_new)
        for i in range(n)
    ]


def _spans(events, name):
    return [e for e in events if e["ph"] == "X" and e["name"] == name]


def _contained(inner, outer) -> bool:
    return (inner["ts"] >= outer["ts"] - 1e-6
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6)


# ---------------------------------------------------------------------------
# recorder semantics
# ---------------------------------------------------------------------------


def test_ring_buffer_evicts_oldest_first():
    rec = obs_trace.Recorder(max_events=4)
    for i in range(6):
        rec.instant(f"e{i}", cat="t")
    # 7 pushes total (thread_name metadata + 6 instants) into 4 slots:
    # the metadata event and e0/e1 fall off the front, oldest first
    assert [e["name"] for e in rec.events()] == ["e2", "e3", "e4", "e5"]
    assert rec.n_dropped == 3
    rec.clear()
    assert len(rec) == 0 and rec.n_dropped == 0


def test_event_forms_and_thread_metadata():
    rec = obs_trace.Recorder(meta={"who": "test"})
    t0 = rec.now()
    rec.complete("work", t0, cat="c", args={"k": 1})
    rec.instant("tick", cat="c")
    rec.counter("depth", 3, cat="c")
    rec.async_begin("req", 7, cat="c")
    rec.async_end("req", 7, cat="c")
    evs = rec.events()
    assert [e["ph"] for e in evs] == ["M", "X", "i", "C", "b", "e"]
    assert evs[0]["args"]["name"]  # thread name captured
    assert evs[1]["dur"] >= 0 and evs[1]["args"] == {"k": 1}
    assert evs[3]["args"]["value"] == 3
    assert evs[4]["id"] == evs[5]["id"] == "7"
    trace = obs_export.validate_trace(obs_export.to_chrome(rec))
    assert trace["metadata"]["who"] == "test"
    assert trace["metadata"]["schema_version"] == obs_export.TRACE_SCHEMA_VERSION


def test_counter_track_is_time_ordered():
    rec = obs_trace.Recorder()
    for v in (1, 2, 3, 5, 8):
        rec.counter("fib", v)
    samples = [e for e in rec.events() if e["ph"] == "C"]
    ts = [e["ts"] for e in samples]
    assert ts == sorted(ts)  # monotone clock -> monotone track
    assert [e["args"]["value"] for e in samples] == [1, 2, 3, 5, 8]


def test_begin_end_span_joins_args_and_takes_given_times():
    rec = obs_trace.Recorder()
    t0 = rec.now()
    sp = rec.begin("work", cat="c", args={"a": 1}, ts=t0)
    sp.end(t0 + 0.25, args={"b": 2})
    rec.begin("bare").end()
    ev, bare = [e for e in rec.events() if e["ph"] == "X"]
    assert ev["name"] == "work" and ev["cat"] == "c"
    assert ev["args"] == {"a": 1, "b": 2}
    assert ev["dur"] == pytest.approx(0.25e6)
    assert bare["dur"] >= 0 and "args" not in bare


def test_start_twice_raises_and_tracing_scopes():
    with obs_trace.tracing() as rec:
        assert obs_trace.active() is rec
        with pytest.raises(RuntimeError):
            obs_trace.start()
    assert obs_trace.active() is None


def test_export_roundtrips_both_formats(tmp_path):
    rec = obs_trace.Recorder(meta={"n": 1})
    rec.instant("a", cat="t", args={"x": 2})
    rec.counter("c", 1.5)
    for name in ("t.json", "t.jsonl"):
        path = str(tmp_path / name)
        written = obs_export.write(rec, path)
        loaded = obs_export.load(path)
        assert loaded["traceEvents"] == written["traceEvents"]
        assert loaded["metadata"]["n"] == 1
        obs_export.validate_trace(loaded)


def test_validate_trace_rejects_malformed():
    ok = {"name": "x", "ph": "i", "ts": 0.0, "pid": 1, "tid": 1, "s": "t"}
    obs_export.validate_trace({"traceEvents": [ok]})
    bad = [
        {**ok, "ph": "Z"},                                  # unknown phase
        {**ok, "ph": "X"},                                  # X without dur
        {**ok, "ph": "X", "dur": -1.0},                     # negative dur
        {**ok, "ph": "b"},                                  # async without id
        {**ok, "ph": "C", "args": {"value": "much"}},       # non-numeric counter
        {**ok, "args": [1, 2]},                             # args not a dict
        {k: v for k, v in ok.items() if k != "ts"},         # missing required
    ]
    for ev in bad:
        with pytest.raises(ValueError):
            obs_export.validate_trace({"traceEvents": [ev]})
    with pytest.raises(ValueError):
        obs_export.validate_trace([ok])  # no envelope


def test_validate_report_rejects_malformed():
    report = obs_analyze.analyze({"traceEvents": []})
    obs_analyze.validate_report(report)
    with pytest.raises(ValueError, match="missing"):
        obs_analyze.validate_report(
            {k: v for k, v in report.items() if k != "decode_ticks"})
    with pytest.raises(ValueError, match="unknown key"):
        obs_analyze.validate_report({**report, "surprise": 1})
    with pytest.raises(ValueError, match="wrong type"):
        obs_analyze.validate_report({**report, "decode_ticks": True})
    with pytest.raises(ValueError, match="not finite"):
        obs_analyze.validate_report(
            {**report, "broadcast_savings_frac": float("nan")})


# ---------------------------------------------------------------------------
# the disabled path: zero events, zero allocations, identical tokens
# ---------------------------------------------------------------------------


def test_tracing_off_records_nothing_and_allocates_nothing(small):
    cfg, params = small
    eng = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=16))
    reqs = _mk_requests(cfg, n=2, max_new=3)
    eng.run([reqs[0]])  # compile outside the measured window
    assert obs_trace.active() is None
    tracemalloc.start()
    try:
        eng.run([reqs[1]])
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    ours = snap.filter_traces(
        [tracemalloc.Filter(True, obs_trace.__file__)]).statistics("lineno")
    assert ours == []  # the disabled path is one global read — no allocations


def test_tracing_onoff_token_streams_identical(small):
    cfg, params = small
    mk = lambda: PagedEngine(cfg, params, config=ServeConfig(  # noqa: E731
        max_slots=2, cache_len=64, page_size=8))
    reqs = _mk_requests(cfg, shared_prefix=16, n=3, max_new=4)
    plain = {r.rid: r.out for r in mk().run(_mk_requests(
        cfg, shared_prefix=16, n=3, max_new=4))}
    with obs_trace.tracing() as rec:
        traced = {r.rid: r.out for r in mk().run(reqs)}
    assert traced == plain  # observation never perturbs the computation
    assert len(rec) > 0


# ---------------------------------------------------------------------------
# instrumentation: nesting + exact counter cross-checks (sync engine)
# ---------------------------------------------------------------------------


def test_engine_trace_cross_checks_live_counters(small):
    cfg, params = small
    eng = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=8))
    reqs = _mk_requests(cfg, shared_prefix=16, n=4, max_new=4)
    with obs_trace.tracing() as rec:
        done = eng.run(reqs)
    assert len(done) == 4
    events = rec.events()
    report = obs_analyze.analyze(obs_export.to_chrome(rec))

    # every engine kernel-call span is inside an engine.step or
    # engine.admit span on the same thread (the worker structure)
    steps = _spans(events, "engine.step")
    admits = _spans(events, "engine.admit")
    decodes = _spans(events, "engine.decode")
    assert steps and admits and decodes
    for d in decodes:
        assert any(_contained(d, s) for s in steps if s["tid"] == d["tid"])
    prefills = (_spans(events, "engine.cold_prefill")
                + _spans(events, "engine.suffix_prefill"))
    assert prefills
    for p in prefills:
        assert any(_contained(p, a) for a in admits if a["tid"] == p["tid"])

    # kernel-call counts: trace == the engine's own per-name counter
    for name, calls in eng.kernel_calls.items():
        assert report[f"kernel_calls_{name}"] == calls
    assert report["kernel_calls_total"] == sum(eng.kernel_calls.values())

    # pool / prefix accounting: trace sums == live counters, exactly
    assert report["pool_pages_allocated"] == eng.pool.stats.allocated
    assert report["pool_pages_freed"] == eng.pool.stats.freed
    assert report["pool_pages_shared"] == eng.pool.stats.shared
    assert report["pool_cow_copies"] == eng.pool.stats.cow_copies
    assert report["prefix_hit_tokens"] == eng.prefix.hit_tokens
    assert report["prefix_miss_tokens"] == eng.prefix.miss_tokens
    assert report["prefix_pages_multicast"] > 0  # the shared prefix hit
    assert report["kernel_calls_decode"] == len(decodes)
    eng.check()


def test_sharded_broadcast_bytes_match_bytes_model(small):
    cfg, params = small
    eng = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=8, num_shards=4,
        pages_per_shard=8, mcast_mode="sw_tree"))
    reqs = _mk_requests(cfg, shared_prefix=32, n=4, max_new=4)
    with obs_trace.tracing() as rec:
        eng.run(reqs)
    report = obs_analyze.analyze(obs_export.to_chrome(rec))
    st = eng.stats()
    assert report["broadcast_chains"] == st["broadcast_chains"] > 0
    assert report["broadcast_pages"] == st["broadcast_pages"]
    assert report["broadcast_payload_bytes"] == st["broadcast_payload_bytes"]
    assert report["broadcast_fabric_bytes"] == st["broadcast_fabric_bytes"]
    # fabric bytes follow dist/mcast's per-device model for the mode...
    mult = mcast.bytes_model(1, 4, per_device=True)["sw_tree"]
    assert report["broadcast_fabric_bytes"] == \
        report["broadcast_payload_bytes"] * mult
    assert report["broadcast_fabric_bytes_sw_tree"] == \
        report["broadcast_fabric_bytes"]
    # ...and beat the all-unicast baseline the analyzer reconstructs
    uni = mcast.bytes_model(1, 4, per_device=True)["unicast"]
    assert report["broadcast_unicast_bytes"] == \
        report["broadcast_payload_bytes"] * uni
    assert 0.0 < report["broadcast_savings_frac"] < 1.0
    assert report["prefix_pages_broadcast"] > 0
    eng.check()


# ---------------------------------------------------------------------------
# the async loop: request spans + TTFT decomposition vs metrics
# ---------------------------------------------------------------------------


def test_loop_trace_ttft_decomposition_matches_metrics(small):
    cfg, params = small
    trace_reqs = LoadGen(seed=3, qps=30.0, duration=0.3, vocab=cfg.vocab,
                         max_new=6, shared_prefix_len=24,
                         shared_frac=0.5).trace()
    eng = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=3, cache_len=128, page_size=16, pages=64))
    with obs_trace.tracing() as rec:
        loop = ServeLoop(eng)
        results = loop.run_trace(trace_reqs)
    assert {r.state for r in results.values()} == {Lifecycle.DRAINED}
    snap = loop.snapshot()
    events = rec.events()
    report = obs_analyze.analyze(obs_export.to_chrome(rec))

    # request lifecycle: one async b/e pair per submitted request
    assert report["requests_submitted"] == len(trace_reqs)
    assert report["requests_finished"] == len(trace_reqs)
    assert report["tokens_emitted"] == snap["tokens_out"]
    assert report["decode_ticks"] == snap["decode_ticks"]

    # nesting: every engine.step span sits inside a decode.tick span
    ticks = _spans(events, "decode.tick")
    for s in _spans(events, "engine.step"):
        assert any(_contained(s, t) for t in ticks if t["tid"] == s["tid"])

    # TTFT decomposition: queue_wait + prefill from span durations must
    # reproduce the metrics histograms (same values, same histogram)
    assert abs(report["ttft_decomposed_p50_ms"] - snap["ttft_p50_ms"]) < 1.0
    assert abs(report["queue_wait_p50_ms"] - snap["queue_wait_p50_ms"]) < 1.0
    # every tick names the slots it decoded, never more than max_slots
    slots = [t["args"]["n_slots"] for t in ticks]
    assert slots and max(slots) <= 3


# ---------------------------------------------------------------------------
# the serve loop's phases and lock waits, on the profiler's clock
# ---------------------------------------------------------------------------

DECODE_PHASES = ("decode.prepare", "engine.decode", "engine.sample")


def _staggered_loop(small, tmp_path=None):
    """Six requests submitted at once to two slots, each with its own
    output length, so slots free one at a time while the other decodes
    and most admissions land between two decode steps.  With
    ``tmp_path`` the run is also under ``jax.profiler``; returns the
    recorder's events and the profiler's log directory."""
    cfg, params = small
    eng = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=16))
    loop = ServeLoop(eng)
    reqs = _mk_requests(cfg, n=7, max_new=3)
    loop.submit(reqs[0].prompt, 3).result(timeout=120)  # compile first
    if tmp_path is not None:
        jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.tracing() as rec:
            handles = [loop.submit(r.prompt, n) for r, n in
                       zip(reqs[1:], (3, 6, 4, 7, 5, 8))]
            loop.close(drain=True)
    finally:
        if tmp_path is not None:
            jax.profiler.stop_trace()
    assert {h.state for h in handles} == {Lifecycle.DRAINED}
    return rec.events()


def _host_plane(log_dir, names) -> dict:
    """{name: [(line, start s, end s)]} of the host plane's events."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {n: [] for n in names}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in out:
                    t = e.start_ns * 1e-9
                    out[e.name].append((i, t, t + e.duration_ns * 1e-9))
    return {n: sorted(v, key=lambda x: x[1]) for n, v in out.items()}


def test_decode_phases_reach_the_profiler_nested_and_timed(small, tmp_path):
    events = _staggered_loop(small, tmp_path)
    names = ("decode.tick", "engine.step", "engine.admit", "decode.emit",
             "decode.wait") + DECODE_PHASES
    host = _host_plane(str(tmp_path), names)
    ticks = host["decode.tick"]
    assert ticks
    for name in names:
        spans = sorted(_spans(events, name), key=lambda e: e["ts"])
        assert len(host[name]) == len(spans) > 0, name
        # the same span on both clocks: equal durations within 1 ms
        for (_, a, b), ev in zip(host[name], spans):
            assert abs((b - a) - ev["dur"] * 1e-6) < 1e-3, name

    def holders(outer, inner):
        return [o for o in host[outer]
                if o[0] == inner[0] and o[1] <= inner[1] and inner[2] <= o[2]]

    # each phase sits inside one decode tick, on the tick's own thread;
    # the other samples are the admissions' first tokens
    for name in ("engine.step",) + DECODE_PHASES:
        in_ticks = [e for e in host[name] if holders("decode.tick", e)]
        assert len(in_ticks) == len(ticks), name
        for e in host[name]:
            assert len(holders("decode.tick", e)
                       + holders("engine.admit", e)) == 1, name
    # and in the recorder, each tick holds one of each phase, in order
    for t in _spans(events, "decode.tick"):
        inside = sorted((e for e in events if e["ph"] == "X"
                         and e["name"] in DECODE_PHASES and _contained(e, t)),
                        key=lambda e: e["ts"])
        assert [e["name"] for e in inside] == list(DECODE_PHASES)
        assert all(e["cat"] == "engine" for e in inside
                   if e["name"] != "engine.decode")


def test_admit_wait_lies_inside_queue_wait(small):
    events = _staggered_loop(small)
    queue = {e["args"]["rid"]: e for e in _spans(events, "request.queue_wait")}
    admit = {e["args"]["rid"]: e for e in _spans(events, "request.admit_wait")}
    prefill = {e["args"]["rid"]: e for e in _spans(events, "request.prefill")}
    assert len(admit) == len(queue) == len(prefill) == 6
    for rid, a in admit.items():
        assert _contained(a, queue[rid])
        # both end where the admission starts
        assert a["ts"] + a["dur"] == pytest.approx(prefill[rid]["ts"], abs=1e-3)
    # a request queued behind a full batch becomes ready when a tick
    # frees a slot: its admit wait starts at that tick's end
    tick_ends = [t["ts"] + t["dur"] for t in _spans(events, "decode.tick")
                 if t["args"]["finished"]]
    behind = [a for rid, a in admit.items()
              if any(abs(a["ts"] - t) < 1e-3 for t in tick_ends)]
    assert behind
    for a in behind:
        assert a["dur"] < queue[a["args"]["rid"]]["dur"]


def test_decode_wait_names_the_admissions_it_waited_for(small):
    events = _staggered_loop(small)
    waits = _spans(events, "decode.wait")
    prefill = {e["args"]["rid"]: e for e in _spans(events, "request.prefill")}
    assert waits
    named = 0
    for w in waits:
        rids = w["args"]["admitted"]
        named += len(rids)
        for rid in rids:
            assert _contained(prefill[rid], w)
    for rid, p in prefill.items():
        for w in waits:
            if _contained(p, w):
                assert rid in w["args"]["admitted"]
    assert named  # admissions landed between two decode steps


def test_loop_tracing_off_allocates_nothing_in_obs(small):
    cfg, params = small
    eng = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=16))
    loop = ServeLoop(eng)
    reqs = _mk_requests(cfg, n=3, max_new=4)
    loop.submit(reqs[0].prompt, 3).result(timeout=120)  # compile first
    tracemalloc.start()
    try:
        for r in reqs[1:]:
            loop.submit(r.prompt, r.max_new)
        loop.close(drain=True)
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    ours = snap.filter_traces(
        [tracemalloc.Filter(True, obs_trace.__file__)]).statistics("lineno")
    assert ours == []


# ---------------------------------------------------------------------------
# analyzer CLI
# ---------------------------------------------------------------------------


def test_analyze_cli_prints_table_and_writes_json(small, tmp_path, capsys):
    cfg, params = small
    eng = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=8))
    with obs_trace.tracing() as rec:
        eng.run(_mk_requests(cfg, shared_prefix=16, n=3, max_new=3))
    tpath, jpath = str(tmp_path / "t.json"), str(tmp_path / "r.json")
    obs_export.write(rec, tpath)
    assert obs_analyze.main([tpath, "--json", jpath]) == 0
    out = capsys.readouterr().out
    assert "prefix_pages_multicast" in out and "kernel_calls_total" in out
    written = json.load(open(jpath))
    assert written == obs_analyze.analyze(tpath)
