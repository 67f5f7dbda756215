"""Work counts against hand-computed ones, and the roofline readers on
hand-made run records: work comes from the request shapes, never from
the kernel's grid."""
import json
import types

import pytest

import devtrace
import flops
import harness
from conftest import BENCH

CFG = json.loads((BENCH / "configs" / "qwen1.5-0.5b.json").read_text())
PEAK = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
decode = harness.load_module(BENCH / "metrics" / "paged_decode_roofline.py")
prefill = harness.load_module(BENCH / "metrics" / "pallas_prefill_roofline.py")


def test_one_decode_row():
    # 16 heads x 64: scores and values 2 * 2 * 16 * 64 per key; the K and
    # V rows of 1000 positions (16 heads x 64 x 2 bytes each) plus one
    # query row in and one output row out
    assert decode.work(CFG, 1000) == (4 * 16 * 64 * 1000,
                                      2 * 1000 * 16 * 64 * 2 + 2 * 16 * 64 * 2)
    assert decode.work(CFG, 1000) == (4_096_000, 4_100_096)


def test_one_decode_call_is_bound_by_memory():
    run = types.SimpleNamespace(cfg=CFG, peak=PEAK)
    # rows of 1000 and 500 tokens over 24 layers: 147,652,608 bytes
    assert decode.least_seconds(run, [1000, 500]) == pytest.approx(
        24 * (4_100_096 + 2_052_096) / 819e9)


def test_one_prefill_chunk():
    # 48 queries at positions 600..647: query i sees 601 + i keys
    keys = sum(601 + i for i in range(48))
    assert keys == 29_976
    assert prefill.work(CFG, 600, 48) == (
        4 * 16 * 64 * keys, 2 * 648 * 16 * 64 * 2 + 2 * 48 * 16 * 64 * 2)
    assert prefill.work(CFG, 600, 48) == (122_781_696, 2_850_816)


def test_model_flops():
    per_layer = 1024 * 64 * (2 * 16 + 2 * 16) + 3 * 1024 * 2816
    assert flops.layer_matmul_params(CFG) == per_layer == 12_845_056
    assert flops.decode_flops(CFG, 1000) == (
        2 * 24 * per_layer + 24 * 4 * 16 * 64 * 1000 + 2 * 1024 * 151936)
    assert flops.prefill_flops(CFG, 0, 3) == (
        2 * 24 * per_layer * 3 + 24 * 4 * 16 * 64 * 6 + 2 * 1024 * 151936)


def _run(trace_ops, reqs, arrivals, spans=(), instants=(), serve=None):
    trace = devtrace.Trace(device={"/device:TPU:0": trace_ops}, host=[])
    return harness.RunData(
        cfg=CFG, serve=serve or {"prefill_chunk": 64}, peak=PEAK, chips=1,
        t0=0.0, t1=10.0, arrivals=arrivals, reqs=reqs, ticks=[],
        spans=list(spans), instants=list(instants), stats={}, trace=trace,
        trace_window=(100.0, 110.0))


def _req(due, times):
    from recorder import Req
    return Req(due=due, tokens=list(times))


def test_decode_roofline_reads_live_lengths_not_the_table():
    arrivals = {0: types.SimpleNamespace(prompt=(0,) * 999, doc=None)}
    # token 0 from the prefill, tokens 1 and 2 from two decode steps with
    # contexts of 1000 and 1001 tokens
    reqs = {0: _req(0.5, [1.0, 2.0, 3.0])}
    kernel = "%_run.7 = bf16[8,16,1,64]{3} custom-call(s32[8,128]{1,0} %t)"
    ops = [devtrace.Event(kernel, 101.0, 101.001, "d"),
           devtrace.Event(kernel, 102.0, 102.001, "d"),
           devtrace.Event("%fusion.3 = f32[8] fusion(%x)", 102.5, 102.6, "d")]
    run = _run(ops, reqs, arrivals)
    least = 24 * (decode.work(CFG, 1000)[1] + decode.work(CFG, 1001)[1]) / 819e9
    assert decode.read(run) == pytest.approx(100 * least / 0.002)


def test_prefill_roofline_counts_real_query_tokens():
    arrivals = {7: types.SimpleNamespace(prompt=(0,) * 700, doc=3)}
    spans = [("engine.admit", 1.0, 1.5, {"rid": 7, "ok": True}),
             ("request.prefill", 1.0, 1.5, {"rid": 7})]
    instants = [("prefix.match", 1.1, {"hit_tokens": 608,
                                        "miss_tokens": 92})]
    ops = [devtrace.Event("%_run.9 = bf16[1,128,16,1,64]{4} custom-call("
                          "s32[1,128]{1,0} %t)", 101.2, 101.21, "d")]
    run = _run(ops, {7: _req(0.9, [1.5])}, arrivals, spans, instants)
    # 92 suffix tokens in chunks of 64 and 28
    least = (prefill.least_seconds(run, 608, 64)
             + prefill.least_seconds(run, 672, 28))
    assert prefill.read(run) == pytest.approx(100 * least / 0.01)


def test_no_kernel_in_the_trace_reads_nothing():
    run = _run([devtrace.Event("fusion.1", 101.0, 102.0, "d")],
               {0: _req(0.5, [1.0, 2.0])},
               {0: types.SimpleNamespace(prompt=(0,) * 10, doc=None)})
    assert decode.read(run) is None and prefill.read(run) is None
