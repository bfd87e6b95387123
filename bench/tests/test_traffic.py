"""The traffic generator on the mixes kept under bench/traffic, and on
open-loop and shared-prefix mixes given as data."""
import itertools

import pytest

from bench import model, traffic
from bench.tests.tiny import ROOT

MIXES = ["chat", "docqa"]


def _take(mix, seed, n, vocab=1000):
    return list(itertools.islice(
        traffic.requests(mix, vocab, model.seed_words(seed)), n))


@pytest.mark.parametrize("name", MIXES)
def test_one_seed_gives_the_same_requests(name):
    mix = traffic.load(ROOT, name)
    assert _take(mix, 2**31 + 3, 40) == _take(mix, 2**31 + 3, 40)


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_differ_within_the_catalog(name):
    mix = traffic.load(ROOT, name)
    a, b = _take(mix, 5, 64), _take(mix, 6, 64)
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    pairs = {tuple(p) for p in mix["pairs"]}
    for r in a + b:
        assert (len(r.prompt), r.output) in pairs and r.session is None
        assert all(1 <= t < 1000 for t in r.prompt)
        assert len(r.prompt) + r.output <= mix["max_total"]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_sends_the_same_sizes_in_the_same_order(name):
    mix = traffic.load(ROOT, name)
    want = [tuple(p) for p in mix["pairs"]] * 2
    for seed in (1, 2, 2**40, -5):
        got = [(len(r.prompt), r.output) for r in _take(mix, seed, len(want))]
        assert got == want


@pytest.mark.parametrize("name", MIXES)
def test_catalog_has_32_pairs_and_clients(name):
    mix = traffic.load(ROOT, name)
    assert len(mix["pairs"]) == 32 and mix["clients"] == 16
    assert mix["loop"] == "closed"
    assert traffic.prompt_lengths(mix) == sorted({p for p, _ in mix["pairs"]})
    warm = traffic.warm_up_requests(mix, 1000)
    assert sorted(len(r.prompt) for r in warm) == traffic.prompt_lengths(mix)


OPEN = {"loop": "open", "rate": 4.0, "arrival_shape": 0.5,
        "catalog_seed": 11, "max_total": 1024,
        "pairs": [[40, 8], [64, 4], [100, 2], [48, 6]],
        "prefix": {"groups": 2, "tokens": 32, "sessions": True}}


def test_open_loop_arrivals_are_the_same_for_every_seed_at_the_rate():
    traffic.check(OPEN)
    a = list(itertools.islice(traffic.arrivals(OPEN), 4000))
    assert a == list(itertools.islice(traffic.arrivals(OPEN), 4000))
    assert a[0] == 0.0 and all(x < y for x, y in zip(a, a[1:]))
    assert len(a) / a[-1] == pytest.approx(OPEN["rate"], rel=0.1)


def test_shared_prefixes_by_group_with_sessions():
    reqs = _take(OPEN, 9, 16)
    heads = {r.session: r.prompt[:32] for r in reqs}
    assert sorted(heads) == ["group-0", "group-1"]
    assert heads["group-0"] != heads["group-1"]
    for n, r in enumerate(reqs):
        assert r.session == f"group-{n % 2}"
        assert r.prompt[:32] == heads[r.session]
    assert _take(OPEN, 10, 4)[0].prompt[:32] != reqs[0].prompt[:32]
    warm = traffic.warm_up_requests(OPEN, 1000)
    lens = traffic.prompt_lengths(OPEN)
    assert [len(r.prompt) for r in warm] == lens + lens
    assert all(r.prompt[:32] == warm[0].prompt[:32] for r in warm[len(lens):])


@pytest.mark.parametrize("bad, match", [
    ({"loop": "replay"}, "loop 'replay'"),
    ({"loop": "closed"}, "needs"),
    ({"clients": 4}, "needs"),
    ({"epoch": 8}, "unknown keys"),
    ({"prefix": {"groups": 2, "tokens": 40}}, "longer than"),
    ({"prefix": {"groups": 2, "tokens": 8, "share": 1}}, "prefix takes"),
    ({"pairs": [[1000, 100]]}, "max_total"),
])
def test_what_the_generator_does_not_honour_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        traffic.check(dict(OPEN, **bad))
