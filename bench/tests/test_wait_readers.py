"""The serving loop's wait readers on hand-made run records: the decode
worker's lock wait before each step, and the admission wait of the
window's admissions; both read nothing from a program without the
spans.  And the split of idle device time by program span."""
import devtrace
import harness
import idle_split
import pytest
from conftest import BENCH
from recorder import Req

decode_wait = harness.load_module(BENCH / "metrics" / "decode_wait_ms.py")
admit_wait = harness.load_module(BENCH / "metrics" / "admit_wait_p50_ms.py")


def _run(spans, reqs=None, trace=None):
    return harness.RunData(
        cfg={}, serve={}, peak={}, chips=1, t0=0.0, t1=10.0, arrivals={},
        reqs=reqs or {}, ticks=[], spans=list(spans), instants=[], stats={},
        trace=trace, trace_window=(100.0, 110.0) if trace else None)


def _tick(s, e):
    return ("decode.tick", s, e, {"n_slots": 2, "finished": 0})


def test_decode_wait_is_the_wait_before_each_step_or_zero():
    spans = [
        _tick(-0.5, -0.3),  # before the window: only bounds the next wait
        ("decode.wait", -0.3, 0.1, {"admitted": [4]}),
        _tick(0.1, 0.3),
        ("decode.wait", 0.3, 0.32, {"admitted": []}),
        _tick(0.32, 0.5),
        # an idle spell: the step after it had no wait before it
        _tick(2.0, 2.2),
        ("decode.wait", 2.2, 2.26, {"admitted": []}),
        _tick(2.26, 2.4),
        ("decode.wait", 9.9, 10.5, {"admitted": []}),  # no step after it
        _tick(10.5, 10.7),  # ends after the window
    ]
    # the window's four steps waited 400, 20, 0 and 60 ms; the step that
    # ends after the window is not counted
    assert decode_wait.read(_run(spans)) == pytest.approx(
        (400 + 20 + 0 + 60) / 4)


def test_admit_wait_is_the_median_over_admissions_started_in_the_window():
    spans = [("request.queue_wait", -1.0, 0.5, {"rid": 1}),
             ("request.admit_wait", -0.2, -0.1, {"rid": 0}),  # before
             ("request.admit_wait", 0.2, 0.5, {"rid": 1}),
             ("request.admit_wait", 1.0, 1.1, {"rid": 2}),
             ("request.admit_wait", 3.0, 3.0, {"rid": 3})]
    assert admit_wait.read(_run(spans)) == pytest.approx(100.0)


def test_a_program_without_the_spans_reads_nothing():
    run = _run([_tick(0.1, 0.3), _tick(0.32, 0.5),
                ("request.queue_wait", 0.0, 0.1, {"rid": 0}),
                ("request.prefill", 0.1, 0.2, {"rid": 0})])
    assert decode_wait.read(run) is None
    assert admit_wait.read(run) is None


def test_idle_split_by_span_request_and_rest():
    ev = devtrace.Event
    # the device idles over [102, 103] and [105, 106] of the window
    ops = [ev("op", 100.0, 102.0, "d"), ev("op", 103.0, 105.0, "d"),
           ev("op", 106.0, 110.0, "d")]
    host = [ev("decode.tick", 101.5, 102.6, "decode"),
            ev("engine.sample", 102.0, 102.5, "decode"),
            ev("decode.wait", 105.0, 105.8, "decode"),
            ev("engine.admit", 105.2, 105.5, "prefill"),
            ev("PjitFunction(f)", 102.7, 102.8, "main")]
    # the program's clock runs 100 s behind the profiler's
    spans = [("decode.tick", 1.5, 2.6, {}),
             ("request.admit_wait", 0.6, 1.2, {"rid": 0}),
             ("request.admit_wait", 4.0, 4.0, {"rid": 1})]
    reqs = {0: Req(due=0.5, admitted=1.2, ended=5.5),
            1: Req(due=3.5, admitted=4.0, ended=5.0)}
    run = _run(spans, reqs, devtrace.Trace(device={"/device:TPU:0": ops},
                                            host=host))
    split = idle_split.idle_split(run)
    assert split["clock_offset_s"] == pytest.approx(100.0)
    assert split["idle_s"] == pytest.approx(2.0)
    assert split["spans"] == pytest.approx({
        "engine.sample": 0.5, "decode.tick": 0.1,
        # the lock holder's span, not the decode worker's wait for it
        "decode.wait": 0.5, "engine.admit": 0.3})
    assert split["no_request"] == pytest.approx(0.2)  # after 105.5
    assert split["unattributed"] == pytest.approx(0.4)
    assert split["unattributed_after"] == pytest.approx({"decode.tick": 0.4})
    queue = idle_split.queue_split(run)
    # queue waits 700 and 500 ms, of which 600 and 0 ms were admit waits
    assert queue["queue_wait_p50_ms"] == pytest.approx(600.0)
    assert queue["admit_wait_p50_ms"] == pytest.approx(300.0)
    assert queue["behind_mean_ms"] == pytest.approx(300.0)
