"""Mesh-sharded serving driver, run by test_dist_serve.py in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=4 (the
main pytest session stays single-device per the dry-run isolation
requirement).

One scenario family: a 4-shard paged engine whose device page arrays are
*actually sharded* over a 4-device CPU mesh serves a shared-prefix
workload, and every mcast mode must produce token streams identical to
the single-host single-shard oracle running in the same process — with
the prefix chain allocated once on its owning shard and broadcast (not
re-prefilled) to the rest, per the engine's counters.  Each model step
runs as one shard_map program per device over its own page block; an
int8 pool leg checks the quantised pages and scales take the same path.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import make_serve_mesh  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve import PagedEngine, Request, ServeConfig  # noqa: E402


def _mk_requests(cfg, *, shared_prefix=32, n=4, max_new=6, seed=7):
    rng = np.random.default_rng(seed)
    prefix = list(rng.integers(0, cfg.vocab, size=shared_prefix))
    return [
        Request(rid=i,
                prompt=prefix + list(rng.integers(0, cfg.vocab, size=3 + i)),
                max_new=max_new)
        for i in range(n)
    ]


def main() -> None:
    assert jax.device_count() == 4, jax.devices()
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    reqs = _mk_requests(cfg)

    oracle = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=8, pages=33))
    clone = lambda: [Request(rid=r.rid, prompt=list(r.prompt),  # noqa: E731
                             max_new=r.max_new) for r in reqs]
    expect = {r.rid: r.out for r in oracle.run(clone())}

    mesh = make_serve_mesh(4)
    for mode in ("unicast", "sw_tree", "hw"):
        eng = PagedEngine(cfg, params, mesh=mesh, config=ServeConfig(
            max_slots=2, cache_len=64, page_size=8, num_shards=4,
            pages_per_shard=8, mcast_mode=mode))
        # the page axis (index 2) of every cache leaf is sharded over
        # the mesh — 4 devices each hold a quarter of the pool's pages
        for leaf in jax.tree.leaves(eng.caches):
            spec = leaf.sharding.spec
            assert spec[2] == "data", (leaf.shape, spec)
            assert all(s is None for i, s in enumerate(spec) if i != 2), spec
            assert len(leaf.sharding.device_set) == 4
        got = {r.rid: r.out for r in eng.run(clone())}
        assert got == expect, (mode, got, expect)
        st = eng.stats()
        # the 4-page prefix chain crossed the fabric once per consumer
        # shard instead of being re-prefilled
        assert st["broadcast_chains"] == 3, st
        assert st["broadcast_pages"] == 12, st
        assert st["prefix_hit_tokens"] == 3 * 32, st
        assert st["broadcast_payload_bytes"] == 12 * eng.page_nbytes, st
        eng.check()
        print(f"OK mesh_serve_{mode}")

    # int8 page pools ride the same per-device program: the quantised
    # pages and their scales are sharded alike
    int8_one = PagedEngine(cfg, params, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=8, pages=33, kv_dtype="int8"))
    expect8 = {r.rid: r.out for r in int8_one.run(clone())}
    eng = PagedEngine(cfg, params, mesh=mesh, config=ServeConfig(
        max_slots=2, cache_len=64, page_size=8, num_shards=4,
        pages_per_shard=8, kv_dtype="int8"))
    got = {r.rid: r.out for r in eng.run(clone())}
    assert got == expect8, (got, expect8)
    eng.check()
    print("OK mesh_serve_int8")

    print("ALL_DISTSERVE_OK")


if __name__ == "__main__":
    main()
