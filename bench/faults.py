"""Faults a one-chip serving cell can have, planted underneath the timed
path: each is called with the engine before the window and breaks it in
place.  ``correct`` has to come out false under every one of them
(``tests/test_correctness.py`` at a tiny size on the CPU, ``control.py
--fault`` at a cell's own size on the chip)."""
import numpy as np


def unchanged_state(engine):
    """A decode step that returns the page pool as it was given: the
    page each row writes is read out before the step and written back
    after it, so the step's own K/V is lost.  The pool stays donated,
    so the fault fits beside a pool that fills the chip."""
    import jax.numpy as jnp

    decode = engine._decode
    page = engine.page_size

    def step(p, caches, toks, index, table, lengths):
        rows = np.arange(np.shape(index)[0])
        ids = jnp.asarray(np.asarray(table)[rows, np.asarray(index) // page])
        before = engine._gather_pages(caches, ids)
        logits, caches = decode(p, caches, toks, index, table, lengths)
        return logits, engine._scatter_pages(caches, ids, before)
    engine._decode = step


def half_batch(engine):
    """A decode step that leaves out half of its live rows (the second
    half by slot): they are not computed, and their logits are zero."""
    decode = engine._decode

    def step(p, caches, toks, index, table, lengths):
        live = np.flatnonzero(np.asarray(lengths) > 0)
        out = live[len(live) // 2:]
        logits, caches = decode(p, caches, toks, index, table,
                                lengths.at[out].set(0))
        return logits.at[out].set(0.0), caches
    engine._decode = step


def altered_token(engine):
    """The sampler changes every third token it produces."""
    select = engine.sampler.select
    calls = [0]

    def pick(logits):
        out = np.array(select(logits))
        calls[0] += 1
        if calls[0] % 3 == 0:
            out = (out + 1) % logits.shape[-1]
        return out
    engine.sampler.select = pick


FAULTS = {f.__name__: f for f in (unchanged_state, half_batch, altered_token)}
