"""Whether what the window served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests the window served, drawn from the seed and holding the
longest of them, is run through the float32 reference once each: over
the prompt and the tokens served.  For every served token the number is
how far the reference's logit of that token lies below the reference's
best logit at that position; the widest such gap is held against its
limit.  Every token is greedy, so a sound program reads only the gaps of
near-ties that its bfloat16 arithmetic breaks the other way.

The sample covers the three paths the timed window ran: a request's
first token comes from its prefill (cold, or a prefix hit's suffix
prefill through ``pallas_prefill``), and the rest from paged decode.
"""
from __future__ import annotations

import sys

import numpy as np

# positions the reference takes at once are padded to a multiple of this
_PAD = 256


def sample(data, seed: int, budget: int) -> list[int]:
    """Rids of served requests, drawn from ``seed``: the longest, the
    first request of a document (cold) and a later one (a prefix hit)
    when the traffic has documents, then others until about ``budget``
    tokens are covered."""
    served = sorted(rid for rid, r in data.reqs.items()
                    if r.tokens and rid in data.arrivals)
    if not served:
        return []
    rng = np.random.default_rng([seed % 2**63, seed // 2**63, 11])
    longest = max(served, key=lambda rid: (len(data.reqs[rid].tokens), -rid))
    pick = [longest]
    seen_doc: dict = {}
    for rid in sorted(served, key=lambda rid: data.reqs[rid].due):
        doc = data.arrivals[rid].doc
        if doc is not None:
            seen_doc.setdefault(doc, []).append(rid)
    cold = [rids[0] for rids in seen_doc.values()]
    hits = [rid for rids in seen_doc.values() for rid in rids[1:]]
    for group in (cold, hits):
        rest = [rid for rid in group if rid not in pick]
        if rest:
            pick.append(int(rng.choice(rest)))
    others = [rid for rid in served if rid not in pick]
    rng.shuffle(others)
    total = sum(len(data.reqs[rid].tokens) for rid in pick)
    for rid in others:
        if total >= budget:
            break
        pick.append(rid)
        total += len(data.reqs[rid].tokens)
    return pick


def _sequences(data, rid: int, width: int, vocab: int):
    """(tokens, next tokens, first served position, served count, ids
    out of the vocabulary) of one request, padded to ``width``."""
    prompt = list(data.arrivals[rid].prompt)
    out = list(data.reqs[rid].out)
    bad = sum(1 for t in out if not 0 <= t < vocab)
    out = [t if 0 <= t < vocab else 0 for t in out]
    n = len(prompt) + len(out)
    seq = np.zeros(width, np.int32)
    seq[:n] = prompt + out
    nxt = np.zeros(width, np.int32)
    nxt[: n - 1] = seq[1:n]
    return seq, nxt, len(prompt) - 1, len(out), bad


def compare(ref, cfg: dict, seed: int, data, limits: dict, *,
            cache_len: int, control: bool = False) -> dict:
    """{number: {"value", "limit"}} for the numbers compared.  With
    ``control``, the float8 control takes the program's place: each
    position's token is the one the control puts first."""
    import jax.numpy as jnp

    vocab = int(cfg["vocab_size"])
    rids = sample(data, seed, int(limits["sample_tokens"]))
    params = ref.make_params(cfg, seed)
    width = -(-cache_len // _PAD) * _PAD
    worst, n_tok, n_off, bad = 0.0, 0, 0, 0
    for rid in rids:
        seq, nxt, first, n, b = _sequences(data, rid, width, vocab)
        if control:
            gaps = ref.control_gaps(params, cfg, jnp.asarray(seq))
        else:
            gaps = ref.token_gaps(params, cfg, jnp.asarray(seq),
                                  jnp.asarray(nxt))
        g = np.asarray(gaps)[first: first + n]
        worst = max(worst, float(g.max()))
        n_tok, n_off, bad = n_tok + n, n_off + int((g > 0).sum()), bad + b
    who = "float8 control" if control else "served"
    print(f"# check: {len(rids)} requests, {n_tok} {who} tokens compared "
          f"with the float32 reference; {n_off} not its first choice; "
          f"widest logit gap {worst}", file=sys.stderr, flush=True)
    return {
        "max_logit_gap": {"value": worst, "limit": limits["max_logit_gap"]},
        "tokens_out_of_vocab": {"value": bad, "limit": 0},
        "empty_sample": {"value": int(n_tok == 0), "limit": 0},
    }
