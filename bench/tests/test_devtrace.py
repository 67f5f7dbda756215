"""The trace-to-metrics reduction, on hand-made events and on a small
trace recorded on the chip."""
import pytest

import devtrace
import harness
from conftest import BENCH
from stats import union_seconds

E = devtrace.Event


def test_busy_is_the_union_of_intervals():
    ops = [E("a", 0.0, 1.0, "d"), E("b", 0.5, 1.5, "d"), E("c", 3.0, 4.0, "d")]
    assert devtrace.busy_seconds(ops) == pytest.approx(2.5)
    assert union_seconds([]) == 0.0


DECODE = "%_run.77 = bf16[8,16,1,64]{3,2,1,0} custom-call(s32[8,128]{1,0} %a)"
PREFILL = ("%_run.9 = bf16[1,128,16,1,64]{4,3,2,1,0} custom-call("
           "s32[1,128]{1,0} %b)")
MATMUL = "%_run.46 = bf16[384,1024]{1,0} custom-call(bf16[384,1024]{1,0} %c)"


def test_kernel_events_by_instruction_shape():
    decode = harness.load_module(BENCH / "metrics" / "paged_decode_roofline.py")
    prefill = harness.load_module(
        BENCH / "metrics" / "pallas_prefill_roofline.py")
    ops = [E(DECODE, 0, 1, "d"), E(PREFILL, 1, 2, "d"), E(MATMUL, 2, 3, "d")]
    assert [e.start for e in devtrace.kernel_events(ops, decode.KERNEL)] == [0]
    assert [e.start for e in devtrace.kernel_events(ops, prefill.KERNEL)] \
        == [1]


def test_top_ops_merge_instances_and_skip_containers():
    ops = [E("%while.5 = (s32[]) while(%t)", 0, 3.5, "d"),
           E("%fusion.1 = f32[2] fusion(%x)", 0, 1, "d"),
           E("%fusion.22 = f32[2] fusion(%y)", 1, 3, "d"),
           E("%copy.4 = bf16[4] copy(%z)", 3, 3.5, "d"), E(DECODE, 4, 5, "d")]
    assert devtrace.top_ops(ops) == [["fusion", 3.0],
                                     ["_run -> bf16[8,16,1,64]", 1.0],
                                     ["copy", 0.5]]


def test_idle_gaps_by_host_activity():
    ops = [E("a", 0.0, 1.0, "d"), E("b", 2.0, 3.0, "d")]
    host = [E("PjitFunction(decode)", 0.9, 1.8, "t"),
            E("bench.window", -1.0, 10.0, "t")]
    # idle 1..2 (host in decode dispatch for 0.8 of it) and 3..4 (nothing)
    assert devtrace.idle_gaps(ops, host, 0.0, 4.0) == [
        ["PjitFunction(decode)", pytest.approx(1.0)],
        ["none", pytest.approx(1.0)]]


def test_idle_share_reader():
    reader = harness.load_module(BENCH / "metrics" / "idle_share.py")
    trace = devtrace.Trace(device={"/device:TPU:0": [E("a", 10.0, 13.0, "d")]},
                           host=[])
    run = type("R", (), {"trace": trace, "trace_window": (10.0, 14.0)})()
    assert reader.read(run) == pytest.approx(25.0)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Ten seconds of ``qwen05b.docqa`` traced on a TPU v5e (seed 101, a
    3841-page pool, 12 requests, 39 decode steps, 3 suffix chunks)."""
    import gzip
    import shutil

    path = tmp_path_factory.mktemp("trace") / "docqa.xplane.pb"
    with gzip.open(BENCH / "tests" / "data" / "docqa_window.xplane.pb.gz") \
            as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return devtrace.load(str(path))


def test_recorded_trace(recorded):
    decode = harness.load_module(BENCH / "metrics" / "paged_decode_roofline.py")
    prefill = harness.load_module(
        BENCH / "metrics" / "pallas_prefill_roofline.py")
    assert list(recorded.device) == ["/device:TPU:0"]
    ops = recorded.device["/device:TPU:0"]
    w0, w1 = harness._trace_window(recorded)
    assert w1 - w0 == pytest.approx(10.000120659)
    # 39 decode steps and 3 suffix-prefill chunks, each once per layer
    dec = devtrace.kernel_events(ops, decode.KERNEL)
    pre = devtrace.kernel_events(ops, prefill.KERNEL)
    assert (len(dec), len(pre)) == (39 * 24, 3 * 24)
    assert sum(e.dur for e in dec) == pytest.approx(2.2865315860000006)
    assert sum(e.dur for e in pre) == pytest.approx(0.05284332300000383)
    busy = devtrace.busy_seconds(ops)
    assert busy == pytest.approx(9.810526898)
    assert devtrace.busy_seconds(devtrace.leaves(ops)) == pytest.approx(
        sum(e.dur for e in devtrace.leaves(ops)))  # leaves never overlap
    top = dict(devtrace.top_ops(ops))
    assert max(top, key=top.get) == "copy"  # the pool copies of decode
    gaps = dict(devtrace.idle_gaps(ops, recorded.host, w0, w1))
    assert sum(gaps.values()) == pytest.approx(w1 - w0 - busy, abs=1e-3)
    assert max(gaps, key=gaps.get) == "np.asarray(jax.Array)"
