"""Draft-token proposers for speculative decoding.

The engine's verify-accept loop (`PagedEngine._step_spec`) is
draft-agnostic: each round it asks a proposer for ``k`` tokens per live
slot, scores all of them in ONE chunked ``decode_step`` on the target
model (the `pallas_prefill` supertile kernel — one multicast KV page
fetch per chunk), and commits the accepted prefix.  Two proposers:

* :class:`ModelDraft` — the classic second-model draft: a small
  same-tokenizer registry pairing (``configs.registry.draft_for``)
  running a dense ring-buffer KV cache, driven through the same
  ``KernelOp`` dispatch as every other model call.  It keeps one cache
  row per engine slot and resyncs a row by bucketed prefill whenever
  the slot's (rid, committed-length) no longer matches — which is
  exactly the fork / preemption / requeue story: any history the draft
  has not seen is replayed from tokens, never trusted.
* :class:`NgramDraft` — prompt-lookup decoding: propose the
  continuation of the most recent matching n-gram from the request's
  own token history.  Zero model cost, so every accepted token is a
  saved target-model dispatch; it shines on self-repetitive streams
  and costs one host-side scan otherwise.

Draft-cache consistency invariant (ModelDraft): after ``observe``,
row ``slot`` holds K/V for exactly the committed tokens
``tokens[:length]`` — rejected draft rows are masked unattendable
(`lm.mask_cache_rows_after`) rather than rewritten, mirroring how the
paged engine leaves stale page rows beyond ``lengths`` for later
overwrite.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.obs import trace
from repro.serve.engine import pad_to_bucket
from repro.serve.sampling import Sampler


@dataclasses.dataclass(frozen=True)
class SlotView:
    """What a proposer may know about a live slot: the request id, the
    full visible token history (committed prefix + the one pending
    token), and the committed K/V length (= ``len(tokens) - 1``)."""

    rid: int
    tokens: tuple[int, ...]
    length: int


class DraftModel:
    """Proposer interface for the engine's verify-accept loop."""

    def propose(self, views: dict[int, SlotView], k: int) -> np.ndarray:
        """Propose ``k`` tokens per slot -> (max_slots, k) int32.

        Rows without a live view are ignored by the engine (fed as
        zeros into the batched verify call)."""
        raise NotImplementedError

    def observe(self, new_lengths: dict[int, int]) -> None:
        """Post-commit notification: slot -> new committed length.
        Stateful drafts roll their caches back here."""

    def forget(self, slot: int) -> None:
        """The slot finished / was preempted; drop draft state."""

    def warmup(self, bucket_lens, k: int) -> int:
        """Pre-compile draft programs; returns number compiled."""
        return 0


class NgramDraft(DraftModel):
    """Prompt-lookup drafting: continue the most recent earlier
    occurrence of the stream's trailing n-gram (longest first, searched
    from the end).  No parameters, no cache — ``observe`` is a no-op
    because the token history IS the state."""

    def __init__(self, max_slots: int, *, max_ngram: int = 3):
        self.max_slots = max_slots
        self.max_ngram = max_ngram

    def _lookup(self, toks: tuple[int, ...], k: int) -> list[int]:
        n = len(toks)
        for nlen in range(min(self.max_ngram, n - 1), 0, -1):
            pat = toks[n - nlen:]
            for start in range(n - nlen - 1, -1, -1):
                if toks[start:start + nlen] == pat:
                    cont = list(toks[start + nlen:start + nlen + k])
                    if cont:
                        return cont + [toks[-1]] * (k - len(cont))
        return [toks[-1]] * k  # no repeat found: guess a constant stream

    def propose(self, views, k):
        out = np.zeros((self.max_slots, k), np.int32)
        for slot, view in views.items():
            out[slot] = self._lookup(tuple(view.tokens), k)
        return out


class ModelDraft(DraftModel):
    """A second, small model proposing greedily from its own dense
    ring-buffer KV cache (one row per engine slot).

    The draft cache is *self-healing*: ``propose`` resyncs any row
    whose tracked (rid, length) disagrees with the engine's view by a
    bucketed prefill over the committed tokens — so slot reuse, forks,
    preemption swaps, and replay-after-fault all reduce to "the draft
    re-reads history", with no cross-module protocol.  After a verify
    round, ``observe`` masks the rejected rows unattendable and keeps
    the accepted ones, leaving every row exactly ``new_length`` long.
    """

    def __init__(self, cfg, params, *, max_slots: int, cache_len: int,
                 prompt_bucket: int = 16, sampler: Sampler,
                 kernel_calls: Optional[Counter] = None):
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.cache_len = cache_len
        self.sampler = sampler
        self.kernel_calls = kernel_calls if kernel_calls is not None else Counter()
        if not all(bd.mixer == "attn" and bd.window is None and bd.ff != "moe"
                   for bd in cfg.layer_defs):
            raise ValueError(
                f"ModelDraft needs a bucket-servable draft (attention-only, "
                f"global windows, non-MoE): {cfg.name}")
        self._bucket = prompt_bucket
        self.caches = lm.init_cache(cfg, max_slots, cache_len)
        self._rid = np.full(max_slots, -1, np.int64)
        self._len = np.zeros(max_slots, np.int32)

        self._decode = jax.jit(lambda p, c, t, i: lm.decode_step(p, cfg, c, t, i))

        def prefill_one(p, t, li, true_len):
            logits, caches = lm.prefill(p, cfg, t, cache_slots=cache_len,
                                        logit_index=li)
            return logits, lm.mask_cache_after(caches, true_len)

        self._prefill_one = jax.jit(prefill_one)
        self._mask_rows = jax.jit(lm.mask_cache_rows_after)

    # ------------------------------------------------------------------
    def _resync(self, slot: int, view: SlotView) -> None:
        ctx = list(view.tokens[:view.length])
        toks = pad_to_bucket(ctx, self._bucket)
        rec = trace.active()
        sp = rec.begin("engine.draft_prefill", cat="kernel") \
            if rec is not None else None
        self.kernel_calls["draft_prefill"] += 1
        _, caches_one = self._prefill_one(
            self.params, jnp.asarray(toks), jnp.int32(len(ctx) - 1),
            jnp.int32(len(ctx)))
        if sp is not None:
            sp.end(args={"slot": slot, "len": len(ctx)})
        self.caches = jax.tree.map(
            lambda full, one: full.at[:, slot:slot + 1].set(one)
            if full.ndim >= 2 else full,
            self.caches, caches_one)
        self._rid[slot] = view.rid
        self._len[slot] = view.length

    def propose(self, views, k):
        for slot, view in views.items():
            if self._rid[slot] != view.rid or self._len[slot] != view.length:
                self._resync(slot, view)
        toks = np.zeros(self.max_slots, np.int32)
        idx = np.zeros(self.max_slots, np.int32)
        for slot, view in views.items():
            toks[slot] = view.tokens[-1]
            idx[slot] = view.length
        drafts = np.zeros((self.max_slots, k), np.int32)
        rec = trace.active()
        for j in range(k):
            sp = rec.begin("engine.draft_decode", cat="kernel") \
                if rec is not None else None
            self.kernel_calls["draft_decode"] += 1
            logits, self.caches = self._decode(
                self.params, self.caches,
                jnp.asarray(toks)[:, None], jnp.asarray(idx))
            if sp is not None:
                sp.end(args={"step": j, "n_slots": len(views)})
            toks = self.sampler.select(logits)[:, -1]
            drafts[:, j] = toks
            idx += 1
        for slot in views:
            self._len[slot] += k
        return drafts

    def observe(self, new_lengths):
        if not new_lengths:
            return
        # mask rejected rows unattendable; untouched slots get a no-op
        # bound (cache positions never reach cache_len)
        bound = np.full(self.max_slots, self.cache_len, np.int32)
        for slot, n in new_lengths.items():
            bound[slot] = n
            self._len[slot] = n
        self.caches = self._mask_rows(self.caches, jnp.asarray(bound))

    def forget(self, slot):
        self._rid[slot] = -1
        self._len[slot] = 0

    def warmup(self, bucket_lens, k: int) -> int:
        compiled = 0
        for blen in sorted(set(bucket_lens)):
            self._prefill_one(
                self.params, jnp.zeros((1, blen), jnp.int32),
                jnp.int32(0), jnp.int32(1))
            compiled += 1
        self._decode(self.params, self.caches,
                     jnp.zeros((self.max_slots, 1), jnp.int32),
                     jnp.zeros(self.max_slots, jnp.int32))
        self._mask_rows(self.caches,
                        jnp.full(self.max_slots, self.cache_len, jnp.int32))
        return compiled + 2


def make_draft(serve_cfg, target_cfg, *, draft=None, max_slots: int,
               cache_len: int, sampler: Sampler,
               kernel_calls: Optional[Counter] = None) -> Optional[DraftModel]:
    """Build the proposer a :class:`~repro.serve.config.ServeConfig`
    asks for (None when speculative decoding is off).

    ``draft`` is the ``(draft_cfg, draft_params)`` pair for model
    drafts; the registry pairing is validated here so an incompatible
    pair fails at engine construction, not mid-stream."""
    if not serve_cfg.spec_k:
        return None
    name = serve_cfg.draft_model
    if name == "ngram":
        return NgramDraft(max_slots)
    from repro.configs import registry
    if draft is None:
        raise registry.DraftPairingError(
            f"draft_model={name!r} needs draft=(cfg, params) at engine "
            f"construction (launch/serve.py initialises it from the "
            f"registry)")
    dcfg, dparams = draft
    registry.validate_draft_pair(target_cfg, dcfg)
    return ModelDraft(dcfg, dparams, max_slots=max_slots,
                      cache_len=cache_len,
                      prompt_bucket=serve_cfg.prompt_bucket,
                      sampler=sampler, kernel_calls=kernel_calls)
