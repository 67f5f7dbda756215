"""Compile the main serving path for a TPU v5e that is described, not
attached: the Pallas kernels at qwen1.5-0.5b widths, one whole
full-width paged decode step, and the 4-device decode step over the
mesh-sharded page pool.  Mosaic refuses here what interpret mode
accepts (misaligned tiles, VMEM overruns, kernels GSPMD cannot
partition).  Nothing runs: these are compiles only.

The topology is described inside a module-scoped fixture (never at
import), and every test skips when no v5e topology can be described.
The persistent compilation cache is off around these compiles.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro import kernels
from repro.configs import get_config
from repro.configs.base import dense_stages
from repro.kernels import api
from repro.models import lm
from repro.serve.engine import per_device

HBM_BYTES = 16 * 2**30  # one v5e chip
PAGE, WIDTH = 16, 128  # page size, block-table width (cache_len 2048)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Dispatch as on the chip: the pallas backend, not interpreted."""
    monkeypatch.setattr(api, "_interpret", lambda: False)


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen1.5-0.5b")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _paged_args(cfg, b, s, num_pages, sharding, *, int8=False):
    kvh, h, d = cfg.attn.n_kv_heads, cfg.attn.n_heads, cfg.attn.head_dim
    pdt = jnp.int8 if int8 else jnp.bfloat16
    args = [
        _sds((b, s, h, d), jnp.bfloat16, sharding),
        _sds((kvh, num_pages, PAGE, d), pdt, sharding),
        _sds((kvh, num_pages, PAGE, d), pdt, sharding),
        _sds((b, WIDTH), jnp.int32, sharding),
        _sds((b,), jnp.int32, sharding),
        _sds((b,), jnp.int32, sharding),
    ]
    if int8:
        args += [_sds((kvh, num_pages, PAGE, 1), jnp.bfloat16, sharding)] * 2
    return args


def test_paged_decode_kernel_compiles(cfg, one_chip, on_tpu):
    args = _paged_args(cfg, 8, 1, 1025, one_chip)
    f = jax.jit(lambda *a: kernels.op("paged_attention")(*a, policy="pallas"))
    assert _custom_calls(f.lower(*args).compile()) == 1


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("s", [16, 64, 256])
def test_pallas_prefill_kernel_compiles(cfg, one_chip, on_tpu, s, int8):
    args = _paged_args(cfg, 1, s, 1025, one_chip, int8=int8)
    f = jax.jit(
        lambda *a: kernels.op("paged_attention")(*a, policy="pallas_prefill"))
    assert _custom_calls(f.lower(*args).compile()) == 1


@pytest.mark.parametrize("m,k,n,bias,act", [
    (8, 1024, 151936, False, None),  # tied-embedding logits
    (256, 1024, 2816, True, "silu"),  # MLP up projection, fused epilogue
    (256, 2816, 1024, False, None),  # MLP down projection
])
def test_tiled_matmul_compiles(one_chip, on_tpu, m, k, n, bias, act):
    x = _sds((m, k), jnp.bfloat16, one_chip)
    w = _sds((k, n), jnp.bfloat16, one_chip)
    b = _sds((n,), jnp.bfloat16, one_chip) if bias else None
    f = jax.jit(lambda x, w, b: kernels.linear(
        x, w, bias=b, activation=act, policy="tiled"))
    assert _custom_calls(f.lower(x, w, b).compile()) >= 1


def _decode_step(cfg, page_axis=None):
    def step(params, caches, toks, index, table, lengths):
        return lm.decode_step(params, cfg, caches, toks, index,
                              block_table=table, lengths=lengths,
                              page_axis=page_axis)
    return step


def _decode_args(cfg, num_pages, b, param_sh, cache_sh, arg_sh):
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, param_sh),
                          lm.abstract(cfg))
    caches = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, cache_sh),
        jax.eval_shape(lambda: lm.init_paged_cache(cfg, num_pages, PAGE)))
    return (params, caches, _sds((b, 1), jnp.int32, arg_sh),
            _sds((b,), jnp.int32, arg_sh), _sds((b, WIDTH), jnp.int32, arg_sh),
            _sds((b,), jnp.int32, arg_sh))


def test_full_width_decode_step_compiles_and_fits(cfg, one_chip, on_tpu):
    args = _decode_args(cfg, 1025, 8, one_chip, one_chip, one_chip)
    compiled = jax.jit(_decode_step(cfg), donate_argnums=(1,)) \
        .lower(*args).compile()
    # per layer: qkv (3) + out + MLP up/gate + down matmuls and the paged
    # decode kernel, inside one scanned body; plus embed-tied logits
    assert _custom_calls(compiled) == 9
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, mem


def test_sharded_decode_step_compiles_without_page_gathers(cfg, topo, on_tpu):
    """The engine's 4-device decode step over the sharded page pool: one
    program per device (shard_map) runs every kernel on local data and
    the paged kernel on its own page block, so no collective moves a
    page array — only the (b, 1, heads, head_dim) attention output of
    each layer is summed across devices."""
    cfg = dataclasses.replace(cfg, n_layers=2, stages=dense_stages(2))
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    num_pages = 4 * (256 + 1)  # 4 shard blocks of a null page + 256
    args = _decode_args(
        cfg, num_pages, 8, NamedSharding(mesh, P()),
        NamedSharding(mesh, P(None, None, "data")), NamedSharding(mesh, P()))
    step = per_device(_decode_step(cfg, "data"), mesh, "data", 6)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert not re.search(r"all-gather|all-to-all", hlo)
    kvh, d = cfg.attn.n_kv_heads, cfg.attn.head_dim
    for line in hlo.splitlines():
        if re.search(r"all-reduce(-start)?\(|collective-permute", line):
            assert f",{PAGE},{d}]" not in line, line  # never a page array
            assert f"{kvh},{d}]" in line or "[]" in line, line
