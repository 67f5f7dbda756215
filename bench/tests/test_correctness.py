"""``correct`` on the CPU at a tiny size: a sound run reads correct, and
the float8 control and each fault the serving cells can have read not
correct.  The harness's look for a chip is skipped; everything else of a
run is driven as on the chip, with the timed path broken underneath."""
import json
import time

import pytest

import faults
import harness
from conftest import BENCH

DATA = BENCH / "tests" / "data"
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
SEED = 2**32 + 977
# the tiny model's limit, set like a cell's: its sound runs read 0.0 here
# and its float8 control 0.053 (test_control_fails)
LIMITS = {"max_logit_gap": 0.02, "sample_tokens": 256}


def _cell(mix: str) -> harness.Cell:
    like = "qwen05b.docqa" if mix == "docqa" else "qwen18b.batch"
    base = harness.resolve(SPEC, like)
    cfg = json.loads((DATA / "tiny.json").read_text())
    cfg["name"] = "tiny"
    return harness.Cell(f"tiny.{mix}", 1, cfg,
                        json.loads((DATA / f"tiny_{mix}.json").read_text()),
                        base.end_to_end, base.per_layer, LIMITS, BENCH)


def _run(mix: str, **kw) -> dict:
    return harness.run(_cell(mix), SEED, 2.0, False,
                       t_start=time.perf_counter(), require_tpu=False, **kw)


@pytest.mark.parametrize("mix", ["docqa", "batch"])
def test_sound_run_is_correct(mix):
    out = _run(mix)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_control_fails():
    out = _run("docqa", control=True)
    assert out["correct"]
    ctl = out["control"]["max_logit_gap"]
    assert ctl["value"] > ctl["limit"], out["control"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("mix", ["docqa", "batch"])
def test_fault_is_not_correct(mix, fault):
    out = _run(mix, fault=faults.FAULTS[fault])
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("seed", [2**31 + 977, 2**32 - 1])
def test_weights_from_seeds_past_31_bits(seed):
    """Seeds from 2**31 up make weights too, and other ones than a
    nearby seed's."""
    import jax
    ref = harness.load_module(BENCH / "configs" / "qwen2_reference.py")
    cfg = json.loads((DATA / "tiny.json").read_text())
    first = [jax.tree_util.tree_leaves(ref.make_params(cfg, s))[0]
             for s in (seed, seed - 1)]
    assert bool(jax.numpy.isfinite(first[0]).all())
    assert not bool((first[0] == first[1]).all())
