"""Compile the main serving path for a TPU v5e that is described, not
attached: the Pallas kernels at qwen1.5-0.5b widths, one whole
full-width paged decode step, the model steps at both benchmark cells'
shapes (checked to leave the page pool in place), and the 4-device
decode step over the mesh-sharded page pool.  Mosaic refuses here what interpret mode
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


def _named(compiled, name: str) -> list[str]:
    """The instructions named after kernel ``name`` (``%name.1 = ...``):
    the name the profiler's trace shows for the kernel's operation."""
    return re.findall(rf"%{name}(?:\.\d+)? = [^=]*? custom-call\(",
                      compiled.as_text())


def _paged_args(cfg, b, s, num_pages, sharding, *, int8=False):
    """A kernel call as the decode step makes it: the stack of every
    layer's pool (bf16 lane-packed; int8 one head per row, a stack of
    the one layer the scan hands it), then the layer index."""
    kvh, h, d = cfg.attn.n_kv_heads, cfg.attn.n_heads, cfg.attn.head_dim
    if int8:
        pool = jax.ShapeDtypeStruct((1, kvh, num_pages, PAGE, d), jnp.int8)
    else:
        pool = jax.eval_shape(lambda: lm.init_paged_cache(
            cfg, num_pages, PAGE))["stage0"]["b0"].k_pages
    args = [
        _sds((b, s, h, d), jnp.bfloat16, sharding),
        _sds(pool.shape, pool.dtype, sharding),
        _sds(pool.shape, pool.dtype, sharding),
        _sds((b, WIDTH), jnp.int32, sharding),
        _sds((b,), jnp.int32, sharding),
        _sds((b,), jnp.int32, sharding),
        _sds((), jnp.int32, sharding),  # the layer
    ]
    if int8:
        args += [_sds((1, kvh, num_pages, PAGE, 1), jnp.bfloat16, sharding)] * 2
    return args


def test_paged_decode_kernel_compiles(cfg, one_chip, on_tpu):
    args = _paged_args(cfg, 8, 1, 1025, one_chip)
    f = jax.jit(lambda *a: kernels.op("paged_attention")(*a, policy="pallas"))
    compiled = f.lower(*args).compile()
    assert _custom_calls(compiled) == 1
    assert len(_named(compiled, "paged_decode")) == 1


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("s", [16, 64, 256])
def test_pallas_prefill_kernel_compiles(cfg, one_chip, on_tpu, s, int8):
    args = _paged_args(cfg, 1, s, 1025, one_chip, int8=int8)
    f = jax.jit(
        lambda *a: kernels.op("paged_attention")(*a, policy="pallas_prefill"))
    compiled = f.lower(*args).compile()
    assert _custom_calls(compiled) == 1
    assert len(_named(compiled, "pallas_prefill")) == 1


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
    # per layer: qkv (3) + out + MLP up/gate + down matmuls, the page
    # write and the paged decode kernel, inside one scanned body; plus
    # embed-tied logits
    assert _custom_calls(compiled) == 10
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, mem


@pytest.mark.parametrize("s", [1, 5, 64, 512])
def test_page_write_kernel_compiles_in_place(cfg, one_chip, on_tpu, s):
    """The page write at a decode row (s 1), a speculative verify (5), a
    suffix chunk (64) and a cold prompt (512): one kernel, and the pools
    it returns are the buffers it was given."""
    pool = jax.eval_shape(lambda: lm.init_paged_cache(
        cfg, 1025, PAGE))["stage0"]["b0"].k_pages
    g, w = pool.shape[1], pool.shape[-1]
    b = 1 if s > 64 else 8
    args = ([_sds(pool.shape, pool.dtype, one_chip)] * 2
            + [_sds((b, s, g, w), jnp.bfloat16, one_chip)] * 2
            + [_sds((b, s), jnp.int32, one_chip)] * 2
            + [_sds((), jnp.int32, one_chip)])
    f = jax.jit(lambda *a: kernels.op("page_write")(*a, policy="pallas"),
                donate_argnums=(0, 1))
    compiled = f.lower(*args).compile()
    assert _custom_calls(compiled) == 1
    assert len(_named(compiled, "page_write")) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
    assert not _pool_ops(compiled.as_text(), 1025)


# the benchmark cells' model steps: (pool pages, decode batch)
CELLS = {"qwen1.5-0.5b": (3073, 8), "qwen1.5-1.8b": (1793, 16)}
POOL_OPS = ("copy", "dynamic-slice", "dynamic-update-slice", "scatter",
            "transpose", "fusion")


def _pool_ops(hlo: str, num_pages: int) -> list[str]:
    """Instructions that copy, slice, relay or scatter a pool-sized
    array (one layer's pool or the stack, by its page and row dims),
    fusions included: the bytes of the pool a step moves besides its
    kernels' reads and writes."""
    shape = rf"\[[\d,]*\b{num_pages},{PAGE},\d+\]"
    op = "|".join(re.escape(o) for o in POOL_OPS)
    return [ln.strip()[:160] for ln in hlo.splitlines()
            if re.search(rf"= \w+{shape}\S* ({op})\(", ln)]


def _pool_layouts(hlo: str, num_pages: int) -> set[str]:
    """The minor-to-major layouts of the entry's pool parameters."""
    return set(re.findall(
        rf"= \w+\[[\d,]*\b{num_pages},{PAGE},\d+\]\{{([\d,]+)[:}}][^=]*"
        rf"parameter\(", hlo))


def _cell_step(cfg, kind, b, s, num_pages, sharding):
    """(jitted step, its argument shapes) of one of the engine's model
    steps at a cell's shape, the pool donated as the engine does."""
    params, caches, *_ = _decode_args(cfg, num_pages, b, sharding, sharding,
                                      sharding)
    if kind == "decode":
        step = _decode_step(cfg)
        rest = (_sds((b, s), jnp.int32, sharding),
                _sds((b,), jnp.int32, sharding),
                _sds((b, WIDTH), jnp.int32, sharding),
                _sds((b,), jnp.int32, sharding))
    else:
        def step(p, c, toks, li, row, length):
            logits, dense = lm.prefill(p, cfg, toks, logit_index=li)
            return logits, lm.prefill_to_pages(dense, c, row, length)
        rest = (_sds((1, s), jnp.int32, sharding),
                _sds((), jnp.int32, sharding),
                _sds((WIDTH,), jnp.int32, sharding),
                _sds((), jnp.int32, sharding))
    return jax.jit(step, donate_argnums=(1,)), (params, caches) + rest


@pytest.mark.parametrize("kind,s", [("decode", 1), ("decode", 64),
                                    ("cold_prefill", 512)])
@pytest.mark.parametrize("arch", sorted(CELLS))
def test_cell_step_leaves_the_pool_in_place(one_chip, on_tpu, arch, kind, s):
    """Both cells' model steps (the decode tick, a 64-token suffix chunk
    through the same decode step, and a cold 512-token prefill written
    to pages) relay no pool bytes: no pool-sized copy, slice, scatter or
    fusion, the pool kept in the row-major layout the kernels read
    (lane-packed at head_dim 64), and the decode step's temporaries well
    under one layer's pool."""
    cfg = get_config(arch)
    num_pages, b = CELLS[arch]
    step, args = _cell_step(cfg, kind, 1 if kind != "decode" else b, s,
                            num_pages, one_chip)
    compiled = step.lower(*args).compile()
    hlo = compiled.as_text()
    assert not _pool_ops(hlo, num_pages), _pool_ops(hlo, num_pages)[:4]
    assert _pool_layouts(hlo, num_pages) == {"4,3,2,1,0"}
    assert len(_named(compiled, "page_write")) == 1
    if kind == "decode":
        # less the f32 output head and logits that _logits builds each
        # step (a separate item: PERF.md section 5), the step holds
        # under 1 GiB of temporaries; the pool is 4.5-5.3 GiB
        head = 4 * cfg.vocab * (cfg.d_model + b * s)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp - head < 2**30, (temp, head)


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
            assert f",{PAGE},128]" not in line, line
            assert f"{kvh},{d}]" in line or "[]" in line, line
