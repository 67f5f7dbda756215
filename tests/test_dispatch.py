"""The KernelOp registry + schedule/backend dispatch (repro.kernels.api):
policy forcing and parsing, off-TPU reference fallback, availability
predicates, the deprecated entry-point shims, and nn-layer forward
parity against pure-einsum references."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.kernels import api, autotune

KEY = jax.random.PRNGKey(7)


@pytest.fixture(autouse=True)
def _fresh_state():
    autotune.clear_cache()
    kernels.set_policy(None)
    yield
    autotune.clear_cache()
    kernels.set_policy(None)


def _ab(m=256, k=128, n=192, dtype=jnp.float32):
    a = jax.random.normal(KEY, (m, k), dtype)
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (k, n), dtype)
    return a, b


# ---------------------------------------------------------------------------
# policy plumbing
# ---------------------------------------------------------------------------


def test_policy_parse_forms():
    assert api.DispatchPolicy.parse("tiled") == api.DispatchPolicy(schedule="tiled")
    # backend names are recognised as backend forcing, not schedules
    assert api.DispatchPolicy.parse("reference") == api.DispatchPolicy(backend="reference")
    assert api.DispatchPolicy.parse("pallas") == api.DispatchPolicy(backend="pallas")
    full = api.DispatchPolicy.parse("schedule=mcast,backend=pallas,autotune=off")
    assert full == api.DispatchPolicy(schedule="mcast", backend="pallas", autotune=False)
    with pytest.raises(ValueError):
        api.DispatchPolicy.parse("speed=ludicrous")
    with pytest.raises(ValueError):
        api.DispatchPolicy(backend="cuda")


def test_policy_env_var_and_global(monkeypatch):
    monkeypatch.setenv(api.POLICY_ENV_VAR, "schedule=unicast")
    name, backend, _, _ = kernels.resolve("matmul", (256, 128, 128), jnp.float32)
    assert (name, backend) == ("unicast", "pallas")
    # set_policy wins over the env var
    kernels.set_policy("tiled")
    name, _, _, _ = kernels.resolve("matmul", (256, 128, 128), jnp.float32)
    assert name == "tiled"
    # and use_policy restores the previous global on exit
    with kernels.use_policy("mcast"):
        assert kernels.resolve("matmul", (256, 128, 128), jnp.float32)[0] == "mcast"
    assert kernels.resolve("matmul", (256, 128, 128), jnp.float32)[0] == "tiled"


def test_forced_schedule_conflicting_backend_raises():
    with pytest.raises(ValueError):
        kernels.resolve(
            "matmul", (256, 128, 128), jnp.float32,
            policy=api.DispatchPolicy(schedule="tiled", backend="reference"),
        )


def test_autotune_off_uses_kernel_defaults():
    _, _, cfg, _ = kernels.resolve(
        "matmul", (512, 256, 256), jnp.float32,
        policy=api.DispatchPolicy(schedule="tiled", autotune=False),
    )
    assert cfg == {}
    a, b = _ab(512, 256, 256)
    out = kernels.linear(
        a, b, policy=api.DispatchPolicy(schedule="tiled", autotune=False)
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(a @ b), rtol=2e-3, atol=2e-3
    )


# ---------------------------------------------------------------------------
# dispatch decisions
# ---------------------------------------------------------------------------


def test_off_tpu_default_is_reference():
    """This container has no TPU: the default policy must fall back to
    the reference backend (never silently interpret-mode pallas)."""
    assert jax.default_backend() != "tpu"
    for op_name, shape in [
        ("matmul", (256, 128, 128)),
        ("flash_attention", (1, 4, 256, 256, 64)),
        ("ssd", (1, 2, 256, 64, 64)),
        ("rglru", (1, 256, 256)),
    ]:
        name, backend, cfg, _ = kernels.resolve(op_name, shape, jnp.float32)
        assert backend == "reference" and cfg == {}, (op_name, name, backend)


def test_backend_pallas_picks_cheapest_available_schedule():
    # small shape: the flat mcast schedule fits VMEM and moves the fewest
    # modeled HBM bytes, so the pallas backend should pick it
    name, backend, _, _ = kernels.resolve(
        "matmul", (256, 256, 256), jnp.float32, policy="pallas"
    )
    assert backend == "pallas"
    p = api.Problem((256, 256, 256), "float32")
    mm = api.op("matmul")
    costs = {
        s.name: s.cost(p) for s in mm.schedules if s.cost and s.available(p)
    }
    assert name == min(costs, key=costs.get)


def test_mcast_availability_predicate_excludes_huge_m():
    """mcast keeps a full-M A/C panel in VMEM — a 65k-row problem cannot
    fit, so availability must exclude it and dispatch must pick tiled."""
    p_small = api.Problem((256, 256, 256), "float32")
    p_huge = api.Problem((65536, 2048, 2048), "float32")
    mcast = api.op("matmul").schedule("mcast")
    assert mcast.available(p_small)
    assert not mcast.available(p_huge)
    name, backend, _, _ = kernels.resolve(
        "matmul", (65536, 2048, 2048), jnp.float32, policy="pallas"
    )
    assert (name, backend) == ("tiled", "pallas")


def test_forced_pallas_backend_never_silently_substitutes_reference():
    """SSD with a (P, N) state too big for VMEM: every pallas candidate
    fails the availability predicate.  Default dispatch falls back to
    reference, but an explicitly forced backend must stay pallas — a
    forced-backend benchmark must never measure the other backend."""
    shape = (1, 1, 256, 2048, 2048)
    p = api.Problem(shape, "float32")
    assert not api.op("ssd").schedule("pallas").available(p)
    assert kernels.resolve("ssd", shape, jnp.float32)[1] == "reference"
    name, backend, _, _ = kernels.resolve(
        "ssd", shape, jnp.float32, policy=api.DispatchPolicy(backend="pallas")
    )
    assert (name, backend) == ("pallas", "pallas")


def test_default_pallas_substitution_is_counted_and_warned(monkeypatch):
    """On the chip (default backend pallas) a problem no pallas schedule
    can take is resolved to the reference backend — visibly: counted in
    ``fallback_stats().substitutions`` on every resolve and warned once
    per (op, problem)."""
    import warnings

    monkeypatch.setattr(api, "_interpret", lambda: False)
    kernels.reset_fallback_stats()
    shape = (1, 1, 256, 2048, 2048)  # SSD state too big for VMEM
    with pytest.warns(RuntimeWarning, match="no pallas schedule of 'ssd'"):
        assert kernels.resolve("ssd", shape, jnp.float32)[1] == "reference"
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # same (op, problem): counted, quiet
        assert kernels.resolve("ssd", shape, jnp.float32)[1] == "reference"
        assert kernels.resolve("ssd", (1, 1, 64, 16, 16), jnp.float32)[1] \
            == "pallas"  # fits: no substitution
    assert kernels.fallback_stats().substitutions == 2
    assert kernels.fallback_stats().fallbacks == 0
    kernels.reset_fallback_stats()
    assert kernels.fallback_stats().substitutions == 0


def test_unknown_op_and_schedule_raise():
    with pytest.raises(ValueError):
        kernels.op("conv2d")
    with pytest.raises(ValueError):
        kernels.resolve("matmul", (8, 8, 8), jnp.float32, policy="warp")
    with pytest.raises(TypeError):
        kernels.op("matmul")(jnp.zeros((8, 8)), jnp.zeros((8, 8)), flavour="spicy")


# ---------------------------------------------------------------------------
# forced-schedule correctness + deprecated shim parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", ["mcast", "tiled", "unicast"])
def test_forced_schedule_matches_reference(schedule):
    a, b = _ab(300, 200, 130)  # nothing divides the blocks
    out = kernels.linear(a, b, policy=schedule)
    ref = kernels.linear(a, b, policy="reference")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_linear_tiled_bitwise_matches_deprecated_wrapper():
    """Acceptance: kernels.linear(policy="tiled") == tiled_matmul exactly."""
    from repro.kernels.matmul.ops import tiled_matmul

    a, b = _ab(512, 256, 384)
    bias = jax.random.normal(jax.random.fold_in(KEY, 2), (384,), jnp.float32)
    new = kernels.linear(a, b, bias=bias, activation="relu", policy="tiled")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        old = tiled_matmul(a, b, bias, activation="relu")
    assert np.array_equal(np.asarray(new), np.asarray(old))


def test_deprecated_wrappers_warn_once_and_stay_correct():
    from repro.kernels.flash_attention.ops import flash
    from repro.kernels.flash_attention.ref import attention_ref

    api._DEPRECATED_SEEN.discard("flash")
    q = jax.random.normal(KEY, (1, 4, 128, 64), jnp.float32)
    kv = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 2, 128, 64), jnp.float32)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        out = flash(q, kv, kv, bq=64, bk=64)
        flash(q, kv, kv, bq=64, bk=64)  # second call: no second warning
    deps = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(deps) == 1 and "flash" in str(deps[0].message)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(attention_ref(q, kv, kv)), rtol=2e-3, atol=2e-3
    )


def test_linear_and_matmul_op_reference_paths_agree():
    """kernels.linear and op("matmul") share one reference epilogue —
    the two entry points must agree bit-for-bit."""
    a, b = _ab(128, 96, 64)
    bias = jax.random.normal(jax.random.fold_in(KEY, 2), (64,), jnp.float32)
    via_linear = kernels.linear(
        a, b, bias=bias, activation="gelu", out_dtype=jnp.bfloat16, policy="reference"
    )
    via_op = kernels.op("matmul")(
        a, b, bias, activation="gelu", out_dtype="bfloat16", policy="reference"
    )
    np.testing.assert_array_equal(np.asarray(via_linear), np.asarray(via_op))


def test_ssd_pallas_default_chunk_divides_odd_lengths():
    """autotune=off must still pick a chunk that divides s (regression:
    the fallback used to be min(128, s) and crashed on s=192)."""
    s = 192
    xdt = jax.random.normal(KEY, (1, 2, s, 32), jnp.float32) * 0.5
    bm = jax.random.normal(jax.random.fold_in(KEY, 3), (1, s, 32), jnp.float32) * 0.5
    log_a = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 5), (1, 2, s)))
    pol = api.DispatchPolicy(schedule="pallas", autotune=False)
    out = kernels.op("ssd")(xdt, bm, bm, log_a, policy=pol)
    ref = kernels.op("ssd")(xdt, bm, bm, log_a, policy="reference")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=5e-4, atol=5e-4)


def test_op_entry_points_pallas_vs_reference():
    """op("ssd") / op("rglru"): the forced pallas schedule agrees with
    the reference backend the CPU default dispatches to."""
    xdt = jax.random.normal(KEY, (1, 2, 256, 64), jnp.float32) * 0.5
    bm = jax.random.normal(jax.random.fold_in(KEY, 3), (1, 256, 64), jnp.float32) * 0.5
    log_a = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 5), (1, 2, 256)))
    ssd = kernels.op("ssd")
    np.testing.assert_allclose(
        np.asarray(ssd(xdt, bm, bm, log_a, policy="pallas")),
        np.asarray(ssd(xdt, bm, bm, log_a)),
        rtol=5e-4, atol=5e-4,
    )

    a = jax.nn.sigmoid(jax.random.normal(KEY, (2, 256, 256))) * 0.2 + 0.8
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 256, 256))
    lru = kernels.op("rglru")
    np.testing.assert_allclose(
        np.asarray(lru(a, x, policy="pallas")),
        np.asarray(lru(a, x)),
        rtol=1e-4, atol=1e-4,
    )


# ---------------------------------------------------------------------------
# linear / grouped_linear semantics
# ---------------------------------------------------------------------------


def test_linear_multidim_weights_and_contraction():
    """Rank-3 weights (headed projections) and contract_dims=2 (output
    projections) must match the einsums they replaced exactly."""
    x = jax.random.normal(KEY, (2, 16, 32), jnp.float32)
    wq = jax.random.normal(jax.random.fold_in(KEY, 1), (32, 4, 8), jnp.float32)
    q = kernels.linear(x, wq)
    np.testing.assert_array_equal(
        np.asarray(q), np.asarray(jnp.einsum("bsd,dnh->bsnh", x, wq))
    )
    wo = jax.random.normal(jax.random.fold_in(KEY, 2), (4, 8, 32), jnp.float32)
    y = kernels.linear(q, wo, contract_dims=2)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(jnp.einsum("bsnh,nhd->bsd", q, wo))
    )
    # pallas path flattens instead; numerics agree within kernel tolerance
    y_t = kernels.linear(q, wo, contract_dims=2, policy="tiled")
    np.testing.assert_allclose(np.asarray(y_t), np.asarray(y), rtol=2e-3, atol=2e-3)


def test_grouped_linear_matches_expert_einsum():
    x = jax.random.normal(KEY, (2, 3, 8, 16), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (3, 16, 12), jnp.float32)
    ref = jnp.einsum("bgmk,gkn->bgmn", x, w)
    np.testing.assert_array_equal(
        np.asarray(kernels.grouped_linear(x, w)), np.asarray(ref)
    )
    got = kernels.grouped_linear(x, w, policy="tiled")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_linear_fused_epilogue_all_schedules():
    a, b = _ab(256, 128, 128)
    bias = jax.random.normal(jax.random.fold_in(KEY, 2), (128,), jnp.float32)
    want = jax.nn.silu(a @ b + bias).astype(jnp.bfloat16)
    for policy in ("reference", "mcast", "tiled", "unicast"):
        got = kernels.linear(
            a, b, bias=bias, activation="silu", out_dtype=jnp.bfloat16, policy=policy
        )
        assert got.dtype == jnp.bfloat16, policy
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=3e-2, atol=3e-2,
        )


# ---------------------------------------------------------------------------
# nn-layer forward parity (new API vs jnp.einsum reference)
# ---------------------------------------------------------------------------


def test_nn_attention_forward_parity():
    """nn attention through the dispatch API vs a hand-rolled einsum
    reference for one GQA config."""
    from repro.configs.base import AttnConfig
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.nn import attention as attn_mod
    from repro.nn.spec import init_params

    cfg = AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16, rope=False)
    d_model = 32
    params = init_params(attn_mod.attn_spec(d_model, cfg), KEY)
    x = jax.random.normal(KEY, (2, 24, d_model), jnp.float32) * 0.5

    got = attn_mod.attention(params, x, cfg, causal=True)

    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"]).transpose(0, 2, 1, 3)
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"]).transpose(0, 2, 1, 3)
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"]).transpose(0, 2, 1, 3)
    o = attention_ref(q, k, v, causal=True).transpose(0, 2, 1, 3)
    want = jnp.einsum("bsnh,nhd->bsd", o, params["wo"])
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3
    )


def test_nn_ssd_forward_parity(monkeypatch):
    """nn SSD block (projections through the dispatch API) vs the same
    block computed with raw einsum projections — the reference backend
    must be a bit-identical drop-in."""
    from repro.configs.base import SsmConfig
    from repro.nn import ssd as nn_ssd
    from repro.nn.spec import init_params

    cfg = SsmConfig(d_state=16, head_dim=8, expand=2, conv_width=4, chunk=8)
    d_model = 32
    params = init_params(nn_ssd.ssd_spec(d_model, cfg), KEY)
    u = jax.random.normal(KEY, (2, 32, d_model), jnp.float32) * 0.5

    got, _ = nn_ssd.ssd(params, u, cfg)

    monkeypatch.setattr(
        kernels, "linear", lambda x, w, **kw: jnp.einsum("...k,kn->...n", x, w)
    )
    want, _ = nn_ssd.ssd(params, u, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_nn_layer_forward_under_forced_pallas_policy():
    """A whole nn forward pass still works (and agrees) when the global
    policy forces the pallas backend in interpret mode."""
    from repro.configs.base import RglruConfig
    from repro.nn import rglru as nn_rglru
    from repro.nn.spec import init_params

    cfg = RglruConfig(d_rnn=128, conv_width=4)
    d_model = 64
    params = init_params(nn_rglru.rglru_spec(d_model, cfg), KEY)
    x = jax.random.normal(KEY, (1, 16, d_model), jnp.float32) * 0.5

    base, _ = nn_rglru.rglru(params, x, cfg)
    with kernels.use_policy("pallas"):
        forced, _ = nn_rglru.rglru(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(base), np.asarray(forced), rtol=2e-2, atol=2e-2
    )


# ---------------------------------------------------------------------------
# vjp capability flag
# ---------------------------------------------------------------------------


@pytest.fixture
def _fake_op():
    """A synthetic family with one VJP-less pallas schedule next to a
    vjp-capable one (all four real families are fully vjp-capable now,
    so the exclusion paths need a fabricated straggler)."""
    fake = api.KernelOp(
        name="fake_op",
        problem=lambda a: a.shape,
        schedules=(
            api.Schedule("novjp", "pallas", lambda a, *, cfg, opts, interpret: a * 2,
                         cost=lambda p: 1.0),
            api.Schedule("withvjp", "pallas", lambda a, *, cfg, opts, interpret: a * 2,
                         cost=lambda p: 2.0, vjp=True),
            api.Schedule("reference", "reference",
                         lambda a, *, cfg, opts, interpret: a * 2, vjp=True),
        ),
    )
    api.register(fake)
    yield fake
    del api._REGISTRY["fake_op"]


def test_resolve_reports_vjp_capability():
    res = kernels.resolve("matmul", (256, 128, 128), jnp.float32, policy="tiled")
    assert res.vjp is True and res.schedule == "tiled"
    # every registered training-path schedule carries a VJP; the one
    # deliberate exception is the paged_attention decode kernel, which
    # is serving-only (nothing differentiates through a decode step)
    for op_name in kernels.ops():
        for sched in api.op(op_name).schedules:
            if op_name == "paged_attention" and sched.backend == "pallas":
                assert not sched.vjp, (op_name, sched.name)
                continue
            assert sched.vjp, (op_name, sched.name)


def test_forced_vjpless_schedule_under_grad_raises(_fake_op):
    x = jnp.ones((8, 8))
    with pytest.raises(ValueError, match="no VJP"):
        jax.grad(lambda x_: _fake_op(x_, policy="novjp").sum())(x)
    # under grad(jit(...)) the inner jit traces before anything
    # differentiates, so eager detection cannot fire — the custom-VJP
    # backstop must still raise the same clear error, not an obscure
    # pallas_call one
    with pytest.raises(ValueError, match="no VJP"):
        jax.grad(jax.jit(lambda x_: _fake_op(x_, policy="novjp").sum()))(x)
    # ...but running it undifferentiated stays fine
    np.testing.assert_array_equal(
        np.asarray(_fake_op(x, policy="novjp")), np.asarray(x * 2)
    )
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda x_: _fake_op(x_, policy="novjp"))(x)),
        np.asarray(x * 2),
    )


def test_auto_dispatch_excludes_vjpless_schedule_under_grad(_fake_op):
    p = api.Problem((8, 8), "float32")
    # undifferentiated: cheapest pallas schedule wins (the vjp-less one)
    sched, _ = _fake_op.resolve(p, api.DispatchPolicy(backend="pallas"))
    assert sched.name == "novjp"
    # under differentiation the same policy falls over to the vjp-capable
    sched, _ = _fake_op.resolve(
        p, api.DispatchPolicy(backend="pallas"), needs_vjp=True
    )
    assert sched.name == "withvjp"
    res = kernels.resolve("fake_op", (8, 8), jnp.float32, policy="pallas",
                          needs_vjp=True)
    assert (res.schedule, res.vjp) == ("withvjp", True)


def test_forced_backend_without_any_vjp_schedule_raises(_fake_op):
    only_novjp = api.KernelOp(
        name="fake_novjp_only",
        problem=lambda a: a.shape,
        schedules=(
            api.Schedule("novjp", "pallas", lambda a, *, cfg, opts, interpret: a,),
            api.Schedule("reference", "reference",
                         lambda a, *, cfg, opts, interpret: a, vjp=True),
        ),
    )
    api.register(only_novjp)
    try:
        with pytest.raises(ValueError, match="no 'pallas' schedule has a VJP"):
            only_novjp.resolve(
                api.Problem((8, 8), "float32"),
                api.DispatchPolicy(backend="pallas"), needs_vjp=True,
            )
        # auto-dispatch (no forced backend) falls back to reference instead
        sched, _ = only_novjp.resolve(
            api.Problem((8, 8), "float32"), None, needs_vjp=True
        )
        assert sched.backend == "reference"
    finally:
        del api._REGISTRY["fake_novjp_only"]


def test_grad_detection_ignores_plain_jit_and_vmap(_fake_op):
    """jit / vmap tracing alone is not differentiation — the vjp-less
    schedule must stay reachable there."""
    x = jnp.ones((8, 8))
    out = jax.jit(lambda x_: _fake_op(x_, policy="novjp"))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x * 2))
    out = jax.vmap(lambda x_: _fake_op(x_, policy="novjp"))(x[None])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(x * 2))
