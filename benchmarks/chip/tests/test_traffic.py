"""Traffic kinds: a seed changes which request is which, not the work.

A closed backlog sends the same sizes in the same order for every seed;
an open loop sends the same multiset of sizes and due times, permuted.
The token ids differ. Each mix's lengths keep the means of the source
it names.
"""
import itertools
import json

import numpy as np
import pytest

import run
from traffic import sizes

SEEDS = (3_000_000_011, 2**31 + 5)


def mix(name):
    return json.load(open(run.HERE / "traffic" / f"{name}.json"))


def test_closed_same_sizes_in_same_order():
    p = mix("offline")
    a, b = (list(itertools.islice(run.traffic_kind(p).make(
        p, s, 51.0, 49152).stream, 300)) for s in SEEDS)
    assert [(len(x), n) for x, n in a] == [(len(x), n) for x, n in b]
    assert [x for x, _ in a] != [x for x, _ in b]


def test_open_loop_same_multiset_other_order():
    p = mix("chat")
    a, b = (run.traffic_kind(p).make(p, s, 51.0, 49152).reqs
            for s in SEEDS)
    assert len(a) == len(b) == round(p["rate_per_s"] * 51.0)
    key = [sorted((len(x), n) for _, x, n in r) for r in (a, b)]
    assert key[0] == key[1]
    kind = run.traffic_kind(p)
    fixed = kind.gaps(len(a))
    fixed = np.sort(fixed * 51.0 / fixed.sum())
    for r in (a, b):            # every gap but the last, which ends it
        g = np.sort(np.diff([t for t, _, _ in r]))
        hit = np.searchsorted(fixed, g - 1e-9)
        np.testing.assert_allclose(fixed[hit], g, rtol=1e-9)
    assert [len(x) for _, x, _ in a] != [len(x) for _, x, _ in b]
    assert all(0 <= t < 51.0 for t, _, _ in a)


@pytest.mark.parametrize("name,means", [("chat", (19.31, 58.45)),
                                        ("offline", (161.31, 337.99))])
def test_lengths_keep_the_source_means(name, means):
    own, out, _ = sizes.sizes(mix(name), 100_000)
    assert own.mean() == pytest.approx(means[0], rel=0.02)
    assert out.mean() == pytest.approx(means[1], rel=0.02)
