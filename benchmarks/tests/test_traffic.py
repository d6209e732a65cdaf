"""The traffic generator: same seed same requests, lengths inside their
clips, the same multiset of work for every seed, due times that do not
depend on completion."""
import numpy as np
import pytest

import traffic


@pytest.mark.parametrize("mix,rate,lo,hi,olo,ohi", [
    ("chat", 2.0, 32, 1536, 16, 384), ("chat", 0.8, 32, 1536, 16, 384)])
def test_schedule(mix, rate, lo, hi, olo, ohi):
    m = traffic.load(mix)
    a = traffic.schedule(m, rate, 60.0, 2_500_000_123, 32768)
    b = traffic.schedule(m, rate, 60.0, 2_500_000_123, 32768)
    c = traffic.schedule(m, rate, 60.0, 7, 32768)
    assert a == b                                   # same seed, same requests
    assert a != c
    for s in (a, c):
        assert all(lo <= len(r.prompt) <= hi for r in s)
        assert all(olo <= r.max_tokens <= ohi for r in s)
        assert all(0 <= t < 32768 for r in s for t in r.prompt[:50])
        due = [r.due_s for r in s]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 60.0
    # every seed offers the same work: same multiset of lengths
    if len(a) == len(c):
        assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
        assert sorted(r.max_tokens for r in a) == sorted(r.max_tokens for r in c)
    assert len(a) == pytest.approx(rate * 60.0, abs=2)


def test_gaps_mean_is_the_rate():
    g = traffic.gaps(500, 2.5)
    assert g.mean() == pytest.approx(0.4)
    assert np.std(g) / g.mean() == pytest.approx(1.0, abs=0.08)   # exponential


def test_check_sample_covers_every_class():
    m = traffic.load("chat")
    cover = [128, 256, 512, 1024, 1536]
    s = traffic.check_sample(m, 12, cover, 16, 5, 32768)
    lens = [len(r.prompt) for r in s[:len(cover)]]
    assert 32 <= lens[0] <= 128
    for ln, lo, hi in zip(lens[1:], cover, cover[1:]):
        assert lo < ln <= hi
    assert len(s) == 12 and all(r.max_tokens == 16 for r in s)
