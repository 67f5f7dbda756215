"""The entry points' process setup: where JAX's persistent compilation
cache goes; the chip smoke test's refusal to run without a TPU, and the
planted faults its logits check must be able to see."""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import runtime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                               restore_cache_dir):
    monkeypatch.setenv(runtime.CACHE_DIR_ENV, str(tmp_path))
    assert runtime.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_cache_defaults_to_one_fixed_dir_in_checkout(monkeypatch,
                                                     restore_cache_dir):
    monkeypatch.delenv(runtime.CACHE_DIR_ENV, raising=False)
    first = runtime.setup_compile_cache()
    assert first == runtime.setup_compile_cache()  # same path every call
    assert first == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def _smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu():
    proc = _smoke(ROOT, os.path.join(ROOT, "chip_smoke.py"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_planted_faults_move_only_decode_logits():
    """The smoke's logits controls must plant a fault the comparison can
    read: each leaves the prefill logits alone and moves the decode
    step's well past the logits tolerance (small model, CPU)."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import Request, ServeConfig

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    config = ServeConfig(max_slots=4, cache_len=128)
    rng = np.random.default_rng(0)
    prefix = list(rng.integers(1, cfg.vocab, 32))
    reqs = [Request(rid=i, prompt=prefix + list(rng.integers(1, cfg.vocab, n)),
                    max_new=2) for i, n in enumerate((20, 37))]
    tape = smoke._Tape()
    sound = smoke.capture_logits(cfg, params, config, reqs, None, tape)
    for fault in (smoke.swap_decode_rows, smoke.drop_decode_page(16)):
        got = smoke.capture_logits(cfg, params, config, reqs, None,
                                   smoke._Tape(replay=tape.chosen),
                                   fault=fault)
        assert [n for n, _ in got] == [n for n, _ in sound]
        for (name, a), (_, b) in zip(got, sound):
            rel = smoke.rel_diff(a, b)
            if name == "decode":
                assert rel > smoke.LOGITS_RTOL, fault
            else:
                assert rel == 0.0, name


def test_chip_smoke_needs_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _smoke(tmp_path, str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
