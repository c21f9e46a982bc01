"""The traffic generator: every seed asks the same work in another order."""

import numpy as np

from bench import spec, traffic

MIX = spec.traffic("glm4-chat.over")


def _lengths(seed):
    seated, arr = traffic.generate(MIX, 151552, seed, 30)
    return seated, arr


def test_same_multiset_other_order():
    """Every seed offers the same schedule (lengths, outputs, times, seated
    contexts); the seed draws the tokens."""
    s1, a1 = _lengths(1)
    s2, a2 = _lengths(2**40 + 7)
    assert [len(r.prompt) for r in a1] == [len(r.prompt) for r in a2]
    assert [r.max_new for r in a1] == [r.max_new for r in a2]
    assert [r.due for r in a1] == [r.due for r in a2]
    assert [(len(r.prompt), r.max_new) for r in s1] == \
        [(len(r.prompt), r.max_new) for r in s2]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a1, a2))
    assert len(s1) == MIX["seat"]


def test_deterministic_in_the_seed():
    _, a = _lengths(5)
    _, b = _lengths(5)
    assert all((x.prompt == y.prompt).all() and x.due == y.due for x, y in zip(a, b))


def test_lengths_respect_limits():
    seated, arr = _lengths(9)
    for r in arr:
        assert MIX["prompt"]["min"] <= len(r.prompt) <= MIX["prompt"]["max"]
        assert MIX["output"]["min"] <= r.max_new <= MIX["output"]["max"]
    for r in seated:
        assert r.max_new >= 1 and len(r.prompt) + r.max_new <= MIX["max_len"]
    dues = [r.due for r in arr]
    assert dues == sorted(dues)
    assert dues[-1] > 30  # the schedule outlasts the window


def test_seated_contexts_do_not_follow_rate_or_window():
    """The seated multiset is one per traffic file: a run at another rate
    or window length seats the same contexts, so their programs stay warm."""
    a, _ = traffic.generate(MIX, 151552, 3, 10)
    b, _ = traffic.generate({**MIX, "rate_rps": 2 * MIX["rate_rps"]}, 151552, 4, 30)
    assert sorted((len(r.prompt), r.max_new) for r in a) == \
        sorted((len(r.prompt), r.max_new) for r in b)
