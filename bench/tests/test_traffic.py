"""The traffic generator: deterministic for a seed, the same sizes on
every seed, every request within the cache."""
import json
from collections import Counter

import pytest

import traffic
from conftest import BENCH

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
SEED = 2**33 + 12345  # wider than 32 signed bits, as the driver's are


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _gen(mix, seed, seconds=30.0, cache_len=2048):
    return traffic.generate(mix, seed=seed, seconds=seconds, vocab=151936,
                            cache_len=cache_len)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    assert _gen(_mix(name), SEED) == _gen(_mix(name), SEED)
    assert _gen(_mix(name), SEED) != _gen(_mix(name), SEED + 1)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_does_the_same_work(name):
    a, b = _gen(_mix(name), SEED), _gen(_mix(name), 7)
    assert Counter(x.max_new for x in a) == Counter(x.max_new for x in b)
    assert sum(len(x.prompt) for x in a) == sum(len(x.prompt) for x in b)
    assert Counter(x.doc for x in a) == Counter(x.doc for x in b)
    assert Counter(x.client for x in a) == Counter(x.client for x in b)

    def gaps(reqs):  # between arrivals, and from the last to the close
        ts = [x.t for x in reqs] + [30.0]
        return sorted(round(q - p, 9) for p, q in zip(ts, ts[1:]))

    assert gaps(a) == gaps(b)  # the same arrivals, in the same order
    assert [(x.doc, len(x.prompt), x.max_new) for x in a] == \
        [(x.doc, len(x.prompt), x.max_new) for x in b]


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seconds", [1.0, 30.0, 51.0])
def test_lengths_fit_the_cache(name, seconds):
    for a in _gen(_mix(name), SEED, seconds):
        assert len(a.prompt) + a.max_new + 1 <= 2048
        assert all(0 <= t < 151936 for t in a.prompt)
        assert 0.0 <= a.t < seconds


def test_a_mix_too_long_for_the_cache_is_refused():
    with pytest.raises(ValueError, match="cache_len"):
        _gen(_mix("docqa"), SEED, cache_len=1024)


def test_open_loop_rate_and_documents():
    mix = _mix("docqa")
    reqs = _gen(mix, SEED, seconds=40.0)
    assert len(reqs) == round(mix["arrivals"]["rate_per_s"] * 40.0)
    assert [a.t for a in reqs] == sorted(a.t for a in reqs)
    by_doc = {}
    for a in reqs:
        by_doc.setdefault(a.doc, set()).add(a.prompt[:256])
    assert all(len(p) == 1 for p in by_doc.values())  # one text per doc
    hot = Counter(a.doc for a in reqs).most_common(1)[0][0]
    assert hot == 0  # rank 1 is the hottest


def test_closed_loop_gives_each_client_a_queue():
    mix = _mix("batch")
    reqs = _gen(mix, SEED)
    clients = mix["arrivals"]["clients"]
    assert len(reqs) == clients * mix["arrivals"]["requests_per_client"]
    assert Counter(a.client for a in reqs) == Counter(
        {c: mix["arrivals"]["requests_per_client"] for c in range(clients)})
    assert all(a.doc is None for a in reqs)


def test_quantiles_are_stratified():
    spec = {"dist": "lognormal", "median": 48, "sigma": 0.7,
            "min": 16, "max": 256}
    vals = traffic.quantiles(spec, 101)
    assert vals == sorted(vals) and vals[50] == 48
    assert min(vals) >= 16 and max(vals) <= 256


def test_zipf_counts():
    counts = traffic.zipf_counts(100, 32, 1.1)
    assert sum(counts) == 100 and counts == sorted(counts, reverse=True)
