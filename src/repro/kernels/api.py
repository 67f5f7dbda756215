"""Unified kernel-op API: a declarative ``KernelOp`` registry with
schedule/backend dispatch.

The paper's point is that the interconnect *schedule* (hw multicast vs.
sw-tree vs. multi-unicast B distribution) is chosen per-transfer by the
system, not hand-picked at every call site.  This module is the kernel
layer's version of that: every kernel family registers its schedules as
declarative :class:`Schedule` entries, and one dispatcher picks the
schedule the way the crossbar picks multicast — automatically, from
shape, dtype and a policy.

Registry layout (one :class:`KernelOp` per family)::

    matmul           mcast | tiled | unicast   (pallas)  + reference
    flash_attention  pallas                              + reference
    ssd              pallas                              + reference
    rglru            pallas                              + reference

Each :class:`Schedule` carries

* an **availability predicate** over the :class:`Problem` (shape/dtype/
  VMEM constraints — e.g. the flat ``mcast`` schedule needs its full-M
  A/C panels to fit VMEM),
* a **cost hook** reusing ``autotune.Candidate.cost`` (modeled HBM bytes
  plus per-grid-step overhead) so the default pick is the cheapest
  available schedule, and
* the **callable** (a thin adapter over the ``pallas_call`` wrapper or
  the pure-jnp ``ref.py`` oracle).

Dispatch resolves, in order: the per-call ``policy=``, then the global
policy (:func:`set_policy` / :func:`use_policy`), then the
``REPRO_KERNEL_POLICY`` environment variable, then the default
:class:`DispatchPolicy` — which runs the Pallas backend on TPU and
transparently falls back to the reference backend everywhere else
(interpret mode is reserved for explicitly forced pallas runs; routing
every model projection through the interpreter would be pathologically
slow).  Block sizes come from the shared autotuner unless the policy
disables it or the caller pins them via ``blocks=``.

Every pallas schedule also carries a **custom VJP** (``Schedule.vjp``),
so ``jax.grad`` through any registry op runs pallas kernels both ways:
matmul backward re-enters dispatch as two more registry matmuls
(dA = g.B^T, dB = A^T.g — the supertile schedules and the autotuner
serve the backward for free), flash-attention backward is the
recompute-based FlashAttention-2 pair of kernels, and ssd/rglru reverse
their scans with the adjoint state carried in VMEM.  Dispatch is
differentiation-aware: under ``jax.grad`` a schedule without a VJP is
auto-excluded (never silently hit), and *forcing* one raises instead of
tracing into an undifferentiable ``pallas_call``.  Only reverse-mode AD
is supported through the pallas backends (``custom_vjp`` functions
cannot be jvp'd, and a raw ``pallas_call`` never could) — use the
reference backend for ``jax.jvp``/``jax.linearize``/forward-over-reverse.

Public surface:

* :func:`linear` — ``act(x @ w + bias)`` for every projection-shaped
  matmul in the model layer (the fused epilogue rides the tiled
  schedule on TPU),
* :func:`grouped_linear` — the per-expert (grouped) form used by MoE,
* :func:`op` — ``op("flash_attention")(q, k, v, causal=...)`` etc.,
* :func:`resolve` — introspection: which schedule/backend/config a call
  would pick and whether it is differentiable (used by tests and
  benchmarks).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import os
import warnings
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.interpreters import ad as _ad

from repro.kernels import autotune
from repro.obs import trace
from repro.kernels.flash_attention.flash_attention import (
    flash_attention,
    flash_attention_bwd_dkv,
    flash_attention_bwd_dq,
)
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.matmul.matmul import (
    _ACTIVATIONS,
    matmul_mcast,
    matmul_mcast_tiled,
    matmul_unicast,
)
from repro.kernels.paged_attention.paged_attention import (
    paged_attention_decode,
    paged_attention_prefill,
)
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.rglru.ref import rglru_scan_ref
from repro.kernels.rglru.rglru import rglru_scan, rglru_scan_bwd
from repro.kernels.ssd.ref import ssd_scan_ref
from repro.kernels.ssd.ssd import ssd_scan, ssd_scan_bwd

POLICY_ENV_VAR = "REPRO_KERNEL_POLICY"
BACKENDS = ("pallas", "reference")
# single source of truth for activation names, shared with the nn layer
# (nn.module.act_fn) so fused-epilogue and out-of-kernel applications of
# the same name can never drift apart
ACTIVATIONS = _ACTIVATIONS


def _interpret() -> bool:
    """Pallas kernels run in interpret mode off-TPU (checked per call so
    tests can monkeypatch the backend)."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# dispatch policy
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DispatchPolicy:
    """How a kernel call resolves its schedule.

    ``schedule``  force a schedule by registry name (e.g. ``"tiled"``);
                  off-TPU a forced pallas schedule runs in interpret mode.
    ``backend``   force ``"pallas"`` or ``"reference"`` — the cheapest
                  available schedule of that backend is picked.
    ``autotune``  ``False`` uses each kernel's default block sizes
                  instead of the shared autotuner.
    """

    schedule: str | None = None
    backend: str | None = None
    autotune: bool = True

    def __post_init__(self):
        if self.backend is not None and self.backend not in BACKENDS:
            raise ValueError(f"unknown backend: {self.backend!r} (have {BACKENDS})")

    @classmethod
    def parse(cls, text: str) -> "DispatchPolicy":
        """Parse ``"tiled"`` / ``"reference"`` shorthands or the full
        ``"schedule=tiled,backend=pallas,autotune=off"`` form (the
        ``REPRO_KERNEL_POLICY`` syntax)."""
        text = text.strip()
        if not text:
            return cls()
        if "=" not in text:
            if text in BACKENDS:
                return cls(backend=text)
            return cls(schedule=text)
        kw: dict[str, Any] = {}
        for item in text.split(","):
            key, _, val = item.partition("=")
            key, val = key.strip(), val.strip()
            if key == "autotune":
                kw[key] = val.lower() not in ("off", "0", "false", "no")
            elif key in ("schedule", "backend"):
                kw[key] = val or None
            else:
                raise ValueError(f"unknown policy field: {key!r} in {text!r}")
        return cls(**kw)


def as_policy(policy: "DispatchPolicy | str | None") -> "DispatchPolicy | None":
    if policy is None or isinstance(policy, DispatchPolicy):
        return policy
    return DispatchPolicy.parse(policy)


_GLOBAL_POLICY: DispatchPolicy | None = None


def set_policy(policy: DispatchPolicy | str | None) -> None:
    """Set the process-wide dispatch policy (None restores the default)."""
    global _GLOBAL_POLICY
    _GLOBAL_POLICY = as_policy(policy)


def get_policy() -> DispatchPolicy:
    """Effective global policy: ``set_policy`` > env var > default."""
    if _GLOBAL_POLICY is not None:
        return _GLOBAL_POLICY
    env = os.environ.get(POLICY_ENV_VAR)
    if env:
        return DispatchPolicy.parse(env)
    return DispatchPolicy()


def _needs_vjp(*arrays) -> bool:
    """True when any input is being differentiated (a ``JVPTracer``
    somewhere in its tracer ancestry — grad/vjp/linearize, possibly
    under jit/vmap).  Dispatch uses this to exclude schedules without a
    VJP *before* tracing into an undifferentiable ``pallas_call``, the
    same way availability predicates exclude VMEM-overflowing schedules.
    Plain jit/vmap tracing is not differentiation and returns False."""
    seen: set[int] = set()
    stack = [x for x in arrays if isinstance(x, jax.core.Tracer)]
    while stack:
        t = stack.pop()
        if isinstance(t, _ad.JVPTracer):
            return True
        for attr in ("val", "primal", "tangent"):  # batching etc. wrappers
            v = getattr(t, attr, None)
            if isinstance(v, jax.core.Tracer) and id(v) not in seen:
                seen.add(id(v))
                stack.append(v)
    return False


@contextlib.contextmanager
def use_policy(policy: DispatchPolicy | str | None):
    """Context manager form of :func:`set_policy` (tests, benchmarks)."""
    global _GLOBAL_POLICY
    prev = _GLOBAL_POLICY
    _GLOBAL_POLICY = as_policy(policy)
    try:
        yield
    finally:
        _GLOBAL_POLICY = prev


# ---------------------------------------------------------------------------
# registry types
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Problem:
    """Static description of one kernel invocation (the autotune key)."""

    shape: tuple[int, ...]
    dtype: str  # dtype name — hashable, jit-static friendly


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One way to run a kernel family.

    ``fn(*arrays, cfg=..., opts=..., interpret=...)`` — ``cfg`` is the
    resolved block-size dict (empty = kernel defaults), ``opts`` the
    family's semantic options (activation, causal, window, ...).
    """

    name: str
    backend: str  # "pallas" | "reference"
    fn: Callable[..., jax.Array]
    available: Callable[[Problem], bool] = lambda p: True
    cost: Callable[[Problem], float] | None = None  # lower wins; None = last resort
    autotune_schedule: str | None = None  # schedule key for autotune.best_config
    # VJP capability: reference schedules differentiate natively (pure
    # jnp), pallas schedules only if wired into the custom-VJP table
    # below.  Under differentiation, dispatch auto-excludes vjp=False
    # schedules and refuses to force one.
    vjp: bool = False


@dataclasses.dataclass(frozen=True)
class KernelOp:
    """A kernel family: its schedules plus the shape/option plumbing."""

    name: str
    schedules: tuple[Schedule, ...]
    problem: Callable[..., tuple[int, ...]]  # (*arrays) -> autotune shape key
    opt_defaults: tuple[tuple[str, Any], ...] = ()

    def schedule(self, name: str) -> Schedule:
        for s in self.schedules:
            if s.name == name:
                return s
        raise ValueError(
            f"kernel op {self.name!r} has no schedule {name!r} "
            f"(have {[s.name for s in self.schedules]})"
        )

    def _normalize_opts(self, opts: dict) -> dict:
        out = dict(self.opt_defaults)
        for key, val in opts.items():
            if key not in out:
                raise TypeError(f"{self.name}() got unexpected option {key!r}")
            out[key] = val
        return out

    def resolve(
        self,
        problem: Problem,
        policy: DispatchPolicy | str | None = None,
        *,
        needs_vjp: bool = False,
    ) -> tuple[Schedule, dict[str, int]]:
        """Pick (schedule, block config) for a problem under a policy.

        ``needs_vjp`` marks a call under differentiation: schedules
        without a VJP are excluded from auto-dispatch, and forcing one
        (by schedule name or backend) raises instead of letting jax die
        deep inside an undifferentiable ``pallas_call``.
        """
        pol = as_policy(policy) or get_policy()
        if pol.schedule is not None:
            sched = self.schedule(pol.schedule)
            if pol.backend is not None and sched.backend != pol.backend:
                raise ValueError(
                    f"policy forces schedule {pol.schedule!r} (backend "
                    f"{sched.backend}) but also backend {pol.backend!r}"
                )
            if needs_vjp and not sched.vjp:
                raise ValueError(
                    f"kernel op {self.name!r}: schedule {sched.name!r} has no "
                    f"VJP but the call is being differentiated (jax.grad / "
                    f"jax.vjp); force a vjp-capable schedule "
                    f"({[s.name for s in self.schedules if s.vjp]}) or drop "
                    f"the forced policy and let dispatch pick one"
                )
        else:
            backend = pol.backend or ("pallas" if not _interpret() else "reference")
            of_backend = [s for s in self.schedules if s.backend == backend]
            if needs_vjp:
                of_backend = [s for s in of_backend if s.vjp]
                if not of_backend and pol.backend is not None:
                    raise ValueError(
                        f"kernel op {self.name!r}: no {pol.backend!r} schedule "
                        f"has a VJP but the call is being differentiated"
                    )
            avail = [s for s in of_backend if s.available(problem)]
            if pol.backend is not None:
                # an explicitly forced backend is honored even when every
                # availability predicate fails (they are conservative
                # models) — silently substituting the other backend would
                # make "force pallas" benchmarks measure XLA numbers
                avail = avail or of_backend
            elif not avail:  # default backend doesn't fit -> reference
                avail = [
                    s for s in self.schedules
                    if s.backend == "reference" and (s.vjp or not needs_vjp)
                ]
                if backend == "pallas":
                    _note_substitution(self.name, problem)
            sched = min(
                avail, key=lambda s: s.cost(problem) if s.cost else math.inf
            )
        cfg: dict[str, int] = {}
        if pol.autotune and sched.autotune_schedule is not None:
            cfg = autotune.best_config(
                self.name, problem.shape, problem.dtype,
                schedule=sched.autotune_schedule,
            )
        return sched, cfg

    def __call__(
        self,
        *arrays: jax.Array,
        policy: DispatchPolicy | str | None = None,
        blocks: dict[str, int] | None = None,
        **opts,
    ) -> jax.Array:
        opts = self._normalize_opts(opts)
        problem = Problem(tuple(self.problem(*arrays)), jnp.dtype(arrays[0].dtype).name)
        pol = as_policy(policy) or get_policy()
        rec = trace.active()
        if rec is None:
            sched, cfg = self.resolve(problem, pol, needs_vjp=_needs_vjp(*arrays))
            return _invoke(self.name, sched, arrays, cfg, blocks, opts, pol)
        t0, n_cached0 = rec.now(), autotune.cache_size()
        sched, cfg = self.resolve(problem, pol, needs_vjp=_needs_vjp(*arrays))
        out = _invoke(self.name, sched, arrays, cfg, blocks, opts, pol)
        _record_dispatch(rec, t0, self.name, sched, problem, cfg, pol, n_cached0)
        return out


_REGISTRY: dict[str, KernelOp] = {}


def register(kernel_op: KernelOp) -> KernelOp:
    _REGISTRY[kernel_op.name] = kernel_op
    return kernel_op


def op(name: str) -> KernelOp:
    """Look up a registered kernel family: ``op("flash_attention")(...)``."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel op: {name!r} (have {sorted(_REGISTRY)})"
        ) from None


def ops() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


class Resolution(NamedTuple):
    """What :func:`resolve` reports: the picked schedule/backend/config
    plus whether that schedule can be differentiated (``vjp``)."""

    schedule: str
    backend: str
    cfg: dict[str, int]
    vjp: bool


def resolve(
    name: str,
    shape: Sequence[int],
    dtype,
    policy: DispatchPolicy | str | None = None,
    *,
    needs_vjp: bool = False,
) -> Resolution:
    """Which (schedule, backend, block config) a call would dispatch to —
    introspection for tests, benchmarks and docs; runs nothing.  Pass
    ``needs_vjp=True`` to see what a differentiated call would pick."""
    sched, cfg = op(name).resolve(
        Problem(tuple(int(s) for s in shape), jnp.dtype(dtype).name),
        policy, needs_vjp=needs_vjp,
    )
    return Resolution(sched.name, sched.backend, cfg, sched.vjp)


def _record_dispatch(rec, t0, op_name, sched, problem, cfg, pol, n_cached0):
    """Record one ``dispatch.<op>`` span on the armed recorder.

    Called per *Python-level* kernel call: under ``jax.jit`` that is
    trace time, so a compiled program records one span per kernel site
    per compilation — the dispatch decisions (schedule, backend, block
    config, autotune outcome), not per-execution timing; the engine's
    ``engine.*`` spans carry the per-call timeline.  ``autotune_cached``
    is derived from the cache-size delta across ``resolve`` and omitted
    when the autotuner was never consulted."""
    args = {
        "op": op_name,
        "schedule": sched.name,
        "backend": sched.backend,
        "shape": list(problem.shape),
        "dtype": problem.dtype,
    }
    for key in ("gm", "bm", "bn", "bk"):
        if key in cfg:
            args[key] = cfg[key]
    if pol.autotune and sched.autotune_schedule is not None:
        args["autotune_cached"] = autotune.cache_size() == n_cached0
    rec.complete(f"dispatch.{op_name}", t0, cat="kernel", args=args)


def _bwd_policy_token(pol: DispatchPolicy) -> str | None:
    """How the backward pass re-dispatches, derived from the forward
    policy.  A per-call forced schedule must not leak to the backward
    problems (dA/dB have different shapes — a forced flat ``mcast``
    could overflow VMEM backward), so forcing pallas in any form pins
    the backward to the cheapest-available *pallas* schedule; otherwise
    the backward resolves under the ambient policy at its own trace
    time (global policy / env var / platform default), which is what
    produced a pallas forward in the first place."""
    if pol.schedule is not None or pol.backend == "pallas":
        return "backend=pallas" + ("" if pol.autotune else ",autotune=off")
    if not pol.autotune:
        return "autotune=off"
    return None


def _invoke(
    op_name: str,
    sched: Schedule,
    arrays: tuple,
    cfg: dict[str, int],
    blocks: dict[str, int] | None,
    opts: dict,
    pol: DispatchPolicy | None = None,
) -> jax.Array:
    """Shared dispatch tail (explicit-block merge + custom-VJP wrap +
    jit trampoline) for ``KernelOp.__call__`` and ``linear``'s pallas
    branch."""
    if blocks:
        cfg = dict(cfg, **{k: v for k, v in blocks.items() if v is not None})
    if sched.backend == "reference":
        cfg = {}  # block choices are meaningless for the oracle
    static = (
        op_name,
        sched.name,
        tuple(sorted(cfg.items())),
        tuple(sorted(opts.items())),
        _interpret(),
        _bwd_policy_token(pol or get_policy()),
    )
    if sched.backend == "pallas":
        # the custom_vjp wrappers are free when nothing differentiates
        # (jax runs the primal below); under jax.grad a vjp-capable
        # schedule routes to the family's backward kernels, and a
        # vjp-less one raises the same clear error resolve() gives —
        # this backstop matters under grad(jit(...)), where the inner
        # jit traces first and _needs_vjp cannot see the later
        # differentiation of the jaxpr
        return (_vjp_call if sched.vjp else _no_vjp_call)(static, *arrays)
    return _run(*arrays, static=static)


@functools.partial(jax.jit, static_argnames=("static",))
def _run(*arrays, static):
    """Single jit'd trampoline for every dispatch — one compile cache per
    (op, schedule, shapes, config, options) so eager callers (tests,
    benchmarks, the deprecated wrappers) pay tracing once per key."""
    op_name, schedule, cfg, opts, interpret, _ = static
    sched = _REGISTRY[op_name].schedule(schedule)
    return sched.fn(*arrays, cfg=dict(cfg), opts=dict(opts), interpret=interpret)


# ---------------------------------------------------------------------------
# custom VJPs — pallas kernels both ways
# ---------------------------------------------------------------------------
#
# One jax.custom_vjp wrapper serves every pallas schedule; the static
# tuple (op, schedule, cfg, opts, interpret, bwd-policy) selects the
# family's forward-with-residuals and backward implementations from the
# tables below.  The primal path is byte-identical to the plain
# dispatch (_run), so wrapping costs nothing when not differentiating.


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _vjp_call(static, *arrays):
    return _run(*arrays, static=static)


def _vjp_fwd(static, *arrays):
    return _VJP_FWD[static[0]](static, *arrays)


def _vjp_bwd(static, residuals, g):
    return _VJP_BWD[static[0]](static, residuals, g)


_vjp_call.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _no_vjp_call(static, *arrays):
    return _run(*arrays, static=static)


def _no_vjp_bwd(static, residuals, g):
    op_name, schedule = static[0], static[1]
    raise ValueError(
        f"kernel op {op_name!r}: schedule {schedule!r} has no VJP but its "
        f"output is being differentiated (jax.grad / jax.vjp); force a "
        f"vjp-capable schedule or let dispatch pick one"
    )


_no_vjp_call.defvjp(lambda static, *arrays: (_no_vjp_call(static, *arrays), ()),
                    _no_vjp_bwd)


def _bwd_blocks(kernel: str, shape, dtype, static, fwd_cfg: dict) -> dict:
    """Backward block config: direction-keyed autotune pick, unless the
    forward policy disabled autotuning (then the forward blocks, which
    at least divide the sequence extents, are reused)."""
    token = static[5]
    if token is not None and "autotune=off" in token:
        return dict(fwd_cfg)
    return autotune.best_config(kernel, shape, dtype, direction="bwd")


# -- matmul: backward re-enters dispatch as two more registry matmuls ------


def _matmul_vjp_fwd(static, a, b, *maybe_bias):
    return _run(a, b, *maybe_bias, static=static), (a, b, *maybe_bias)


def _matmul_vjp_bwd(static, res, g):
    a, b, *maybe_bias = res
    bias = maybe_bias[0] if maybe_bias else None
    opts = dict(static[3])
    pol = static[5]  # bwd dispatch policy token (None = ambient)
    g32 = g.astype(jnp.float32)
    if opts["activation"] != "none":
        # recompute the pre-activation z (one dispatched matmul) — the
        # FlashAttention trade: one extra pass instead of an (M, N)
        # fp32 residual written to HBM on every forward
        z = linear(a, b, out_dtype=jnp.float32, policy=pol)
        if bias is not None:
            z = z + bias.astype(jnp.float32)
        _, act_vjp = jax.vjp(_ACTIVATIONS[opts["activation"]], z)
        dz = act_vjp(g32)[0]
    else:
        dz = g32
    grads = (
        linear(dz.astype(a.dtype), b.T, policy=pol).astype(a.dtype),  # g.B^T
        linear(a.T, dz.astype(a.dtype), policy=pol).astype(b.dtype),  # A^T.g
    )
    if bias is not None:
        grads += (dz.sum(axis=0).astype(bias.dtype),)
    return grads


# -- flash attention: FlashAttention-2 recompute backward ------------------


def _flash_vjp_fwd(static, q, k, v):
    _, _, cfg, opts, interpret, _ = static
    opts = dict(opts)
    o, lse = flash_attention(
        q, k, v, causal=opts["causal"], window=opts["window"],
        softcap=opts["softcap"], **dict(cfg), return_lse=True,
        interpret=interpret,
    )
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(static, res, g):
    _, _, cfg, opts, interpret, _ = static
    opts = dict(opts)
    q, k, v, o, lse = res
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    blocks = _bwd_blocks(
        "flash_attention", (b, h, sq, sk, d), q.dtype, static, dict(cfg)
    )
    kw = dict(
        causal=opts["causal"], window=opts["window"], softcap=opts["softcap"],
        **blocks, interpret=interpret,
    )
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dq = flash_attention_bwd_dq(q, k, v, g, lse, delta, **kw)
    dk, dv = flash_attention_bwd_dkv(q, k, v, g, lse, delta, **kw)
    if h != kvh:  # GQA: per-query-head gradients sum onto the kv heads
        group = h // kvh
        dk = dk.reshape(b, kvh, group, sk, d).sum(axis=2)
        dv = dv.reshape(b, kvh, group, sk, d).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# -- ssd: reverse-chunk adjoint scan ----------------------------------------


def _ssd_chunk(cfg: dict, s: int) -> int:
    """The kernel asserts chunk | s: autotuned pick, else the largest
    divisor <= 128 (shared by forward dispatch and the VJP)."""
    return cfg.get("chunk") or max(
        d for d in range(1, min(128, s) + 1) if s % d == 0
    )


def _ssd_lcum(log_a, chunk: int):
    bsz, h, s = log_a.shape
    lc = log_a.reshape(bsz, h, s // chunk, chunk)
    return jnp.cumsum(lc, axis=-1).reshape(bsz, h, s, 1)


def _ssd_vjp_fwd(static, xdt, b, c, log_a):
    _, _, cfg, _, interpret, _ = static
    chunk = _ssd_chunk(dict(cfg), log_a.shape[-1])
    lcum = _ssd_lcum(log_a, chunk)
    y, states = ssd_scan(
        xdt, b, c, lcum, chunk=chunk, return_states=True, interpret=interpret
    )
    return y, (xdt, b, c, log_a, states)


def _ssd_vjp_bwd(static, res, g):
    _, _, cfg, _, interpret, _ = static
    xdt, b, c, log_a, states = res
    s = log_a.shape[-1]
    fwd_chunk = _ssd_chunk(dict(cfg), s)
    # the checkpointed states are one per *forward* chunk, so the
    # backward kernel must walk the same chunk grid — direction-keyed
    # autotune applies to the other families, whose residuals are
    # chunk-agnostic
    lcum = _ssd_lcum(log_a, fwd_chunk)
    dx, db_h, dc_h, dl = ssd_scan_bwd(
        xdt.astype(jnp.float32), b.astype(jnp.float32), c.astype(jnp.float32),
        lcum, states, g.astype(jnp.float32),
        chunk=fwd_chunk, interpret=interpret,
    )
    return (
        dx.astype(xdt.dtype),
        db_h.sum(axis=1).astype(b.dtype),  # B/C are head-shared
        dc_h.sum(axis=1).astype(c.dtype),
        dl[..., 0].astype(log_a.dtype),
    )


# -- rglru: reverse linear scan ---------------------------------------------


def _rglru_vjp_fwd(static, a, b):
    _, _, cfg, _, interpret, _ = static
    h = rglru_scan(a, b, **dict(cfg), interpret=interpret)
    return h, (a, h)


def _rglru_vjp_bwd(static, res, g):
    _, _, cfg, _, interpret, _ = static
    a, h = res
    blocks = _bwd_blocks("rglru", a.shape, jnp.float32, static, dict(cfg))
    h_prev = jnp.concatenate(
        [jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1
    )
    da, db = rglru_scan_bwd(
        a.astype(jnp.float32), h_prev, g.astype(jnp.float32),
        **blocks, interpret=interpret,
    )
    # the kernel streams a and b as one fp32 recurrence; their
    # cotangents come back in the (shared) input dtype
    return da.astype(a.dtype), db.astype(a.dtype)


_VJP_FWD = {
    "matmul": _matmul_vjp_fwd,
    "flash_attention": _flash_vjp_fwd,
    "ssd": _ssd_vjp_fwd,
    "rglru": _rglru_vjp_fwd,
}
_VJP_BWD = {
    "matmul": _matmul_vjp_bwd,
    "flash_attention": _flash_vjp_bwd,
    "ssd": _ssd_vjp_bwd,
    "rglru": _rglru_vjp_bwd,
}


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def _fits_vmem(kernel: str, schedule: str = "default") -> Callable[[Problem], bool]:
    """Availability: some block candidate stays inside the VMEM budget."""

    def ok(p: Problem) -> bool:
        cands = autotune.candidates(kernel, p.shape, p.dtype, schedule=schedule)
        return min(c.vmem_bytes for c in cands) <= autotune.VMEM_BUDGET

    return ok


def _model_cost(kernel: str, schedule: str = "default") -> Callable[[Problem], float]:
    """Cost hook: the best candidate's ``autotune.Candidate.cost``."""

    def cost(p: Problem) -> float:
        return autotune.candidates(kernel, p.shape, p.dtype, schedule=schedule)[0].cost

    return cost


def _out_dtype(opts: dict, fallback) -> jnp.dtype:
    return jnp.dtype(opts["out_dtype"]) if opts["out_dtype"] is not None else jnp.dtype(fallback)


# ---------------------------------------------------------------------------
# matmul family
# ---------------------------------------------------------------------------


def _mm_flat(kernel_fn):
    """mcast/unicast don't fuse the epilogue in-kernel; bias + activation
    + downcast run unfused (fp32) after the pallas_call."""

    def fn(a, b, *maybe_bias, cfg, opts, interpret):
        bias = maybe_bias[0] if maybe_bias else None
        y = kernel_fn(a, b, **cfg, interpret=interpret)
        if bias is not None or opts["activation"] != "none":
            y = y.astype(jnp.float32)
            if bias is not None:
                y = y + bias.astype(jnp.float32)
            y = _ACTIVATIONS[opts["activation"]](y)
        return y.astype(_out_dtype(opts, a.dtype))

    return fn


def _mm_tiled(a, b, *maybe_bias, cfg, opts, interpret):
    bias = maybe_bias[0] if maybe_bias else None
    return matmul_mcast_tiled(
        a, b, bias, **cfg,
        activation=opts["activation"],
        out_dtype=opts["out_dtype"],
        interpret=interpret,
    )


def _reference_epilogue(y, bias, opts):
    """Reference-backend epilogue, shared by ``linear`` and the 2-D
    ``op("matmul")`` path.  Deliberately keeps the pre-dispatch
    model-layer numerics (``out_dtype`` cast *before* the bias add,
    activation in that dtype) rather than the kernels' fused fp32
    epilogue: routing-sensitive consumers (MoE top-k) calibrated their
    decode-vs-forward noise floor against exactly these rounding points."""
    y = y.astype(_out_dtype(opts, y.dtype))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return _ACTIVATIONS[opts["activation"]](y)


def _mm_reference(a, b, *maybe_bias, cfg, opts, interpret):
    bias = maybe_bias[0] if maybe_bias else None
    return _reference_epilogue(jnp.dot(a, b), bias, opts)


register(KernelOp(
    name="matmul",
    problem=lambda a, b, *rest: (a.shape[0], a.shape[1], b.shape[1]),
    opt_defaults=(("activation", "none"), ("out_dtype", None)),
    schedules=(
        Schedule("tiled", "pallas", _mm_tiled,
                 cost=_model_cost("matmul", "tiled"), autotune_schedule="tiled",
                 vjp=True),
        Schedule("mcast", "pallas", _mm_flat(matmul_mcast),
                 available=_fits_vmem("matmul", "mcast"),
                 cost=_model_cost("matmul", "mcast"), autotune_schedule="mcast",
                 vjp=True),
        Schedule("unicast", "pallas", _mm_flat(matmul_unicast),
                 cost=_model_cost("matmul", "unicast"), autotune_schedule="unicast",
                 vjp=True),
        Schedule("reference", "reference", _mm_reference, vjp=True),
    ),
))


def linear(
    x: jax.Array,
    w: jax.Array,
    *,
    bias: jax.Array | None = None,
    activation: str | None = None,
    out_dtype=None,
    contract_dims: int = 1,
    policy: DispatchPolicy | str | None = None,
    blocks: dict[str, int] | None = None,
) -> jax.Array:
    """``act(x @ w + bias)`` through the dispatched matmul schedule.

    The single entry point for every projection-shaped matmul in the
    model layer: on TPU the tiled multicast schedule fuses the epilogue
    into the kernel flush (no extra HBM round trip); off-TPU it runs the
    reference backend with the model layer's original XLA numerics.

    ``x``: (..., *k_dims); ``w``: (*k_dims, *out_dims) with
    ``contract_dims`` leading axes contracted (e.g. attention's
    ``o @ wo`` contracts (heads, head_dim)); ``bias`` broadcasts over
    ``out_dims``.  Dispatch resolves on the flattened (M, K, N) problem,
    but the reference backend runs an *unflattened* ``dot_general`` —
    bit- and HLO-identical to the pre-registry einsum/``@`` call sites,
    so GSPMD sharding decisions (and MoE top-k routing rounding) are
    unchanged off-TPU.  The pallas backends flatten to 2-D for the
    kernel grid.  ``out_dtype`` defaults to ``x.dtype`` (pallas) / the
    dot's natural result dtype (reference).
    """
    k_dims, out_dims = w.shape[:contract_dims], w.shape[contract_dims:]
    lead = x.shape[: x.ndim - contract_dims]
    m = math.prod(lead)
    k, n = math.prod(k_dims), math.prod(out_dims)
    out_name = jnp.dtype(out_dtype).name if out_dtype is not None else None
    opts = {"activation": activation or "none", "out_dtype": out_name}

    mm = op("matmul")
    pol = as_policy(policy) or get_policy()
    problem = Problem((m, k, n), jnp.dtype(x.dtype).name)
    rec = trace.active()
    t0 = rec.now() if rec is not None else 0.0
    n_cached0 = autotune.cache_size() if rec is not None else 0
    sched, cfg = mm.resolve(problem, pol, needs_vjp=_needs_vjp(x, w, bias))
    if sched.backend == "reference":
        # contracting dims listed high-to-low: einsum's canonical order,
        # so this lowers bit-identically to the einsum/@ sites it replaced
        contract = (
            tuple(reversed(range(x.ndim - contract_dims, x.ndim))),
            tuple(reversed(range(contract_dims))),
        )
        y = jax.lax.dot_general(x, w, (contract, ((), ())))
        if rec is not None:
            _record_dispatch(rec, t0, "matmul", sched, problem, cfg, pol,
                             n_cached0)
        return _reference_epilogue(y, bias, opts)

    arrays = (x.reshape(m, k), w.reshape(k, n))
    if bias is not None:
        arrays += (bias.reshape(n),)
    y = _invoke("matmul", sched, arrays, cfg, blocks, opts, pol)
    if rec is not None:
        _record_dispatch(rec, t0, "matmul", sched, problem, cfg, pol, n_cached0)
    return y.reshape(*lead, *out_dims)


def grouped_linear(
    x: jax.Array,
    w: jax.Array,
    *,
    activation: str | None = None,
    policy: DispatchPolicy | str | None = None,
) -> jax.Array:
    """Per-group linear (the MoE expert matmul): ``x``: (..., g, m, k),
    ``w``: (g, k, n) -> (..., g, m, n) — one independent matmul per group.

    The reference backend keeps the GShard einsum form (GSPMD shards the
    group axis without resharding); the pallas backends run one dispatched
    2-D matmul per group.
    """
    g, k, n = w.shape
    lead = x.shape[:-3]
    m = x.shape[-2]
    m_eff = max(1, math.prod(lead)) * m
    _, backend, _, _ = resolve(
        "matmul", (m_eff, k, n), x.dtype, policy, needs_vjp=_needs_vjp(x, w)
    )
    if backend == "reference":
        y = jnp.einsum("...gmk,gkn->...gmn", x, w)
        if activation is not None:
            y = _ACTIVATIONS[activation](y)
        return y
    # one vmapped kernel over the group axis (pallas_call lifts the
    # batch dim into its grid) — schedule/config resolve once at trace
    xt = x.reshape(-1, g, m, k).transpose(1, 0, 2, 3).reshape(g, -1, k)
    y = jax.vmap(
        lambda xi, wi: linear(xi, wi, activation=activation, policy=policy)
    )(xt, w)
    return y.reshape(g, -1, m, n).transpose(1, 0, 2, 3).reshape(*lead, g, m, n)


# ---------------------------------------------------------------------------
# flash attention family
# ---------------------------------------------------------------------------


def _flash_pallas(q, k, v, *, cfg, opts, interpret):
    return flash_attention(
        q, k, v, causal=opts["causal"], window=opts["window"],
        softcap=opts["softcap"], **cfg, interpret=interpret,
    )


def _flash_reference(q, k, v, *, cfg, opts, interpret):
    return attention_ref(
        q, k, v, causal=opts["causal"], window=opts["window"],
        softcap=opts["softcap"],
    )


register(KernelOp(
    name="flash_attention",
    # q: (b, h, sq, d); k/v: (b, kvh, sk, d) -> autotune key (b, h, sq, sk, d)
    problem=lambda q, k, v: (*q.shape[:3], k.shape[2], q.shape[3]),
    opt_defaults=(("causal", True), ("window", None), ("softcap", None)),
    schedules=(
        Schedule("pallas", "pallas", _flash_pallas,
                 available=_fits_vmem("flash_attention"),
                 cost=_model_cost("flash_attention"), autotune_schedule="default",
                 vjp=True),
        Schedule("reference", "reference", _flash_reference, vjp=True),
    ),
))


# ---------------------------------------------------------------------------
# paged attention family (serving decode against a paged KV pool)
# ---------------------------------------------------------------------------


def _paged_pallas(q, k_pages, v_pages, block_table, start, lengths, *scales,
                  cfg, opts, interpret):
    if q.shape[1] != 1 or scales:
        # only a by-name forced policy can land here: availability routes
        # multi-token / int8 problems to the supertile schedule
        raise ValueError(
            "paged_attention: schedule 'pallas' is the single-token bf16/"
            "fp32 decode kernel; multi-token and int8 calls run the "
            "'pallas_prefill' supertile schedule (backend='pallas' picks "
            "it automatically)"
        )
    o = paged_attention_decode(
        q[:, 0], k_pages, v_pages, block_table, start, lengths,
        softcap=opts["softcap"], interpret=interpret,
    )
    return o[:, None]


def _paged_prefill_pallas(q, k_pages, v_pages, block_table, start, lengths,
                          *scales, cfg, opts, interpret):
    k_scale, v_scale = scales if scales else (None, None)
    return paged_attention_prefill(
        q, k_pages, v_pages, block_table, start, lengths,
        k_scale=k_scale, v_scale=v_scale, softcap=opts["softcap"],
        qc=cfg.get("qc"), interpret=interpret,
    )


def _paged_reference(q, k_pages, v_pages, block_table, start, lengths, *scales,
                     cfg, opts, interpret):
    k_scale, v_scale = scales if scales else (None, None)
    return paged_attention_ref(
        q, k_pages, v_pages, block_table, start, lengths,
        softcap=opts["softcap"], k_scale=k_scale, v_scale=v_scale,
    )


_paged_fits = _fits_vmem("paged_attention")
_paged_prefill_fits = _fits_vmem("paged_attention", "prefill")

register(KernelOp(
    name="paged_attention",
    # q: (b, s, h, d); pages: (kvh, P, ps, d); table: (b, pages_per_seq);
    # start/lengths: (b,).  Trailing flag: number of scale arrays (int8
    # pools pass 2 — the availability predicates read it, since opts
    # can't see arity)
    problem=lambda q, kp, vp, bt, st, ln, *scales: (
        q.shape[0], q.shape[1], q.shape[2], kp.shape[0],
        bt.shape[1], kp.shape[2], q.shape[3], len(scales),
    ),
    opt_defaults=(("softcap", None),),
    schedules=(
        # single-token bf16/fp32 decode kernel: the cheapest pick for
        # the steady-state decode problem it is shaped for
        Schedule("pallas", "pallas", _paged_pallas,
                 available=lambda p: (
                     p.shape[1] == 1 and p.shape[-1] == 0 and _paged_fits(p)
                 ),
                 cost=_model_cost("paged_attention"), vjp=False),
        # chunked-prefill supertile kernel: any s (prefix-hit suffix
        # prefills) and int8 pages (fused dequant-on-gather) — one K/V
        # page fetch multicast across the q chunk
        Schedule("pallas_prefill", "pallas", _paged_prefill_pallas,
                 available=_paged_prefill_fits,
                 cost=_model_cost("paged_attention", "prefill"),
                 autotune_schedule="prefill", vjp=False),
        Schedule("reference", "reference", _paged_reference, vjp=True),
    ),
))


# ---------------------------------------------------------------------------
# ssd family
# ---------------------------------------------------------------------------


def _ssd_pallas(xdt, b, c, log_a, *, cfg, opts, interpret):
    chunk = _ssd_chunk(cfg, log_a.shape[-1])
    lcum = _ssd_lcum(log_a, chunk)
    return ssd_scan(xdt, b, c, lcum, chunk=chunk, interpret=interpret)


def _ssd_reference(xdt, b, c, log_a, *, cfg, opts, interpret):
    return ssd_scan_ref(xdt, b, c, log_a)


register(KernelOp(
    name="ssd",
    problem=lambda xdt, b, c, log_a: (*xdt.shape[:3], xdt.shape[3], b.shape[-1]),
    schedules=(
        Schedule("pallas", "pallas", _ssd_pallas,
                 available=_fits_vmem("ssd"),
                 cost=_model_cost("ssd"), autotune_schedule="default",
                 vjp=True),
        Schedule("reference", "reference", _ssd_reference, vjp=True),
    ),
))


# ---------------------------------------------------------------------------
# rglru family
# ---------------------------------------------------------------------------


def _rglru_pallas(a, b, *, cfg, opts, interpret):
    return rglru_scan(a, b, **cfg, interpret=interpret)


def _rglru_reference(a, b, *, cfg, opts, interpret):
    return rglru_scan_ref(a, b)


register(KernelOp(
    name="rglru",
    problem=lambda a, b: a.shape,
    schedules=(
        Schedule("pallas", "pallas", _rglru_pallas,
                 available=_fits_vmem("rglru"),
                 cost=_model_cost("rglru"), autotune_schedule="default",
                 vjp=True),
        Schedule("reference", "reference", _rglru_reference, vjp=True),
    ),
))


# ---------------------------------------------------------------------------
# degradation: retry-once-on-reference kernel fallback
# ---------------------------------------------------------------------------
#
# Serving robustness (repro.serve): a pallas kernel call that raises —
# or, under the opt-in numeric check, produces NaN/Inf — is retried
# exactly once on the reference backend of the same op instead of
# crashing the whole batch.  The mechanism lives here (next to the
# dispatch it guards); the *policy* of when to arm it is the caller's
# (`PagedEngine(kernel_fallback=True)`, `--kernel-fallback`).  Fallbacks
# are counted so a degraded-but-alive server is visible in stats rather
# than silently slow.


@dataclasses.dataclass
class FallbackStats:
    """Cumulative counters for :func:`call_with_fallback`, plus the
    auto-dispatch substitutions: calls whose default pallas backend had
    no available schedule and were resolved to the reference backend
    instead (counted per resolve, i.e. per kernel site per trace)."""

    calls: int = 0  # guarded calls attempted
    fallbacks: int = 0  # calls that completed on the reference retry
    raised: int = 0  # primary raised an exception
    numeric_trips: int = 0  # primary returned non-finite output
    substitutions: int = 0  # default-pallas resolves that picked reference
    last_error: str | None = None


_FALLBACK_STATS = FallbackStats()
_SUBSTITUTED: set[tuple[str, Problem]] = set()


def _note_substitution(op_name: str, problem: Problem) -> None:
    """Count one reference substitution on the pallas default backend and
    warn once per (op, problem): on the chip it means XLA, not the
    kernel, runs that site."""
    _FALLBACK_STATS.substitutions += 1
    key = (op_name, problem)
    if key in _SUBSTITUTED:
        return
    _SUBSTITUTED.add(key)
    warnings.warn(
        f"repro.kernels: no pallas schedule of {op_name!r} is available for "
        f"shape {problem.shape} {problem.dtype}; the reference backend runs "
        f"it instead", RuntimeWarning, stacklevel=4)


def fallback_stats() -> FallbackStats:
    """Snapshot of the process-wide fallback counters."""
    return dataclasses.replace(_FALLBACK_STATS)


def reset_fallback_stats() -> None:
    global _FALLBACK_STATS
    _FALLBACK_STATS = FallbackStats()
    _SUBSTITUTED.clear()


def all_finite(*arrays) -> bool:
    """Opt-in output guard: True iff every float array is NaN/Inf-free.
    Host-synchronising by design — callers run it at batch boundaries
    (the serving engine already syncs there to read the sampled token),
    never inside a jit trace."""
    for a in arrays:
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating):
            if not bool(jnp.isfinite(a).all()):
                return False
    return True


def call_with_fallback(primary, reference, *args, check=None):
    """Run ``primary(*args)``; on an exception — or, when ``check`` is
    given, on ``check(out)`` returning False — run ``reference(*args)``
    once and return its result instead.

    Returns ``(out, fell_back)``.  The reference retry is *not* guarded:
    if the oracle backend also fails, the problem is not a kernel
    mis-dispatch and the error propagates.  Callers must not donate the
    input buffers to ``primary`` (a failed primary would leave nothing
    for the retry to consume)."""
    _FALLBACK_STATS.calls += 1
    try:
        out = primary(*args)
    except Exception as e:  # noqa: BLE001 — any kernel failure degrades
        _FALLBACK_STATS.raised += 1
        _FALLBACK_STATS.last_error = f"{type(e).__name__}: {e}"
    else:
        if check is None or check(out):
            return out, False
        _FALLBACK_STATS.numeric_trips += 1
        _FALLBACK_STATS.last_error = "non-finite kernel output"
    _FALLBACK_STATS.fallbacks += 1
    rec = trace.active()
    if rec is not None:
        rec.instant("kernel.fallback", cat="kernel",
                    args={"error": _FALLBACK_STATS.last_error})
    return reference(*args), True


# ---------------------------------------------------------------------------
# deprecation shim support (the old per-kernel ops.py entry points)
# ---------------------------------------------------------------------------

_DEPRECATED_SEEN: set[str] = set()


def warn_deprecated(name: str, replacement: str) -> None:
    """One DeprecationWarning per entry point per process."""
    if name in _DEPRECATED_SEEN:
        return
    _DEPRECATED_SEEN.add(name)
    warnings.warn(
        f"repro.kernels: {name} is deprecated; use {replacement}",
        DeprecationWarning,
        stacklevel=3,
    )
