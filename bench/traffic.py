"""Traffic generator: one general reader of the mix files in ``traffic/``.

Started from the program's seeded Poisson generator (``serve/loadgen.py``
``LoadGen``) and kept here, so that no change to the program can move
the yardstick.  Extended with what that generator lacked: lognormal
lengths, a pool of shared documents with Zipf popularity and
closed-loop clients.

A mix file is JSON:

    {"arrivals": {"process": "poisson", "rate_per_s": 1.2}
              |  {"process": "closed", "clients": 32,
                  "requests_per_client": 8},
     "documents": {"count": 32, "zipf_s": 1.1, "length": LENGTH},  # optional
     "prompt": LENGTH,   # the request's own tokens, after its document
     "output": LENGTH,
     "shape_seed": 0}

    LENGTH = {"dist": "lognormal", "median": 48, "sigma": 0.7,
              "min": 16, "max": 256}

Every seed gets the same requests in the same order, but other tokens:
sizes are stratified quantiles of their distribution, arrival gaps the
stratified quantiles of the exponential, and their order (which request
gets which size, when it is due, which document it asks about) is drawn
from ``shape_seed``; ``--seed`` draws only the token ids (and the run's
weights).  Runs on different seeds then do the same work in the same
order, so their spread measures the system and not the draw: a queueing
tail over some tens of requests moves by a factor of several with the
order alone.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request.  ``t`` is seconds from the window's start (open loop);
    closed-loop requests carry ``client`` and the position in its queue
    instead, and are due when the client's previous request ends."""

    rid: int
    t: float
    prompt: tuple[int, ...]
    max_new: int
    doc: int | None = None  # index of the shared document it opens with
    client: int | None = None


def quantiles(spec: dict, n: int) -> list[int]:
    """``n`` stratified draws of a lognormal length distribution: the
    values at the quantiles (i + 0.5) / n, rounded and clipped to
    [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = int(spec["min"]), int(spec["max"])
    if not 1 <= lo <= hi:
        raise ValueError(f"bad length range {spec!r}")
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    vals = [math.exp(mu + sigma * _NORMAL.inv_cdf((i + 0.5) / n))
            for i in range(n)]
    return [min(hi, max(lo, int(round(v)))) for v in vals]


def zipf_counts(n: int, count: int, s: float) -> list[int]:
    """Requests per document for ``n`` requests over ``count`` documents
    of Zipf popularity ``s`` (rank 1 the hottest), by largest remainder."""
    w = [1.0 / (k + 1) ** s for k in range(count)]
    total = sum(w)
    exact = [n * x / total for x in w]
    out = [int(e) for e in exact]
    for k in sorted(range(count), key=lambda k: exact[k] - out[k],
                    reverse=True)[: n - sum(out)]:
        out[k] += 1
    return out


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**63, seed // 2**63, stream])


def _arrival_times(n: int, seconds: float,
                   rng: np.random.Generator) -> list[float]:
    """Poisson due times in [0, seconds): stratified unit-exponential
    gaps in the mix's order, scaled so the ``n`` requests span the
    window."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    rng.shuffle(gaps)
    cum = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [float(x) * seconds / float(np.sum(gaps)) for x in cum]


def generate(mix: dict, *, seed: int, seconds: float, vocab: int,
             cache_len: int) -> list[Arrival]:
    """The requests of one run, in due order (open loop) or in client
    queue order (closed loop).  Raises when a request could not fit
    ``cache_len``."""
    arr = mix["arrivals"]
    proc = arr["process"]
    shape = np.random.default_rng(int(mix.get("shape_seed", 0)))
    order = np.random.default_rng([int(mix.get("shape_seed", 0)), 1])
    tokens = _rng(seed, 1)
    docs = mix.get("documents")
    doc_tokens: list[tuple[int, ...]] = []
    if docs is not None:
        lens = quantiles(docs["length"], int(docs["count"]))
        shape.shuffle(lens)  # which rank gets which length: fixed
        doc_tokens = [tuple(int(x) for x in tokens.integers(0, vocab, n))
                      for n in lens]
    worst = (int(mix["prompt"]["max"]) + int(mix["output"]["max"]) + 1
             + (int(docs["length"]["max"]) if docs is not None else 0))
    if worst > cache_len:
        raise ValueError(f"a request of this mix can need {worst} tokens, "
                         f"more than cache_len {cache_len}")

    if proc == "closed":
        clients = int(arr["clients"])
        rounds = int(arr["requests_per_client"])
        n = clients * rounds
        # each round of one request per client is a whole stratified set
        p_lens, o_lens, doc_ids = [], [], []
        for _ in range(rounds):
            for seq, spec in ((p_lens, mix["prompt"]), (o_lens, mix["output"])):
                vals = quantiles(spec, clients)
                order.shuffle(vals)
                seq.extend(vals)
            doc_ids.extend(_doc_ids(docs, clients, order))
        times = [0.0] * n
        owner = [i % clients for i in range(n)]
    elif proc == "poisson":
        n = max(1, int(round(float(arr["rate_per_s"]) * seconds)))
        p_lens = quantiles(mix["prompt"], n)
        o_lens = quantiles(mix["output"], n)
        order.shuffle(p_lens)
        order.shuffle(o_lens)
        doc_ids = _doc_ids(docs, n, order)
        times = _arrival_times(n, seconds, order)
        owner = [None] * n
    else:
        raise ValueError(f"unknown arrival process {proc!r}")

    out = []
    for i in range(n):
        own = tuple(int(x) for x in tokens.integers(0, vocab, p_lens[i]))
        doc = doc_ids[i]
        prompt = (doc_tokens[doc] if doc is not None else ()) + own
        out.append(Arrival(rid=i, t=times[i], prompt=prompt,
                           max_new=o_lens[i], doc=doc, client=owner[i]))
    return out


def _doc_ids(docs: dict | None, n: int,
             rng: np.random.Generator) -> list[int | None]:
    if docs is None:
        return [None] * n
    counts = zipf_counts(n, int(docs["count"]), float(docs["zipf_s"]))
    ids = [k for k, c in enumerate(counts) for _ in range(c)]
    rng.shuffle(ids)
    return ids
