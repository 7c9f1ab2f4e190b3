"""The traffic generator: determinism, the designed shapes of the mixes,
and the arrival schedule."""

import collections

import pytest

from bench import generator, spec

VOCAB = 151936
BLOCK = 16


def _chat(seed, scale=1):
    return generator.Traffic(spec.traffic("chat-shared"), VOCAB, BLOCK, seed, scale=scale)


def test_same_seed_same_traffic_other_seed_other_tokens():
    a, b, c = _chat(7), _chat(7), _chat(8)
    pa = [a.next().tokens for _ in range(60)]
    assert pa == [b.next().tokens for _ in range(60)]
    assert [p.tokens for p in a.warmup()] == [p.tokens for p in b.warmup()]
    assert pa != [c.next().tokens for _ in range(60)]
    assert a.arrivals(3.0, 40) == b.arrivals(3.0, 40)


def test_chat_shared_lengths_and_designed_cached_share():
    t = _chat(11)
    assert t.lengths() == [768, 1280, 1792, 2304, 2816]
    warm = t.warmup()
    # sessions start evenly over turns 1..5; those past turn 1 are warmed
    assert len(warm) == 48 - 10  # ten sessions start at turn 1
    prompts = [t.next() for _ in range(48 * 20)]
    assert {len(p.tokens) for p in prompts} == set(t.lengths())
    # every round of 48 visits each session once: the same lengths each 5 rounds
    counts = collections.Counter(len(p.tokens) for p in prompts)
    assert max(counts.values()) - min(counts.values()) <= 48 // 5 + 1
    share = sum(p.shared for p in prompts) / sum(len(p.tokens) for p in prompts)
    # by design 6656 / 8960 = 0.743 of prompt tokens are held by an earlier
    # prompt; first uses of a system prompt fall a little short of it
    assert 0.70 <= share <= 0.743
    for p in prompts:
        assert p.shared % BLOCK == 0 and p.shared < len(p.tokens)


def test_turns_extend_the_previous_prompt():
    t = _chat(3)
    for _ in range(200):
        p = t.next()
        if len(p.tokens) > 768:
            assert p.shared >= len(p.tokens) - 512  # the session's last prompt
        else:
            assert p.shared in (0, 512)  # its system prompt, if used before


def test_open_loop_schedule_same_gaps_every_seed():
    a, b = _chat(1).arrivals(3.0, 40.0), _chat(2).arrivals(3.0, 40.0)
    assert len(a) == len(b) == 120
    assert a[0] == b[0] == 0.0 and max(a) < 40.0
    # the schedule belongs to the mix, not to the seed
    assert a == b
    gaps = sorted(y - x for x, y in zip(a, a[1:]))
    assert gaps != [y - x for x, y in zip(a, a[1:])]  # shuffled, not sorted
    assert abs(sum(gaps) / 119 - 1 / 3.0) < 0.02


def test_every_seed_gets_the_same_work():
    a, b = _chat(21), _chat(22)
    pa, pb = [a.next() for _ in range(150)], [b.next() for _ in range(150)]
    assert [len(p.tokens) for p in pa] == [len(p.tokens) for p in pb]
    assert [p.shared for p in pa] == [p.shared for p in pb]
    assert [len(p.tokens) for p in a.warmup()] == [len(p.tokens) for p in b.warmup()]
    assert all(x.tokens != y.tokens for x, y in zip(pa, pb))
    mix = dict(spec.traffic("chat-shared"), schedule_seed=5)
    c = generator.Traffic(mix, VOCAB, BLOCK, 21)
    assert [len(c.next().tokens) for _ in range(150)] != [len(p.tokens) for p in pa]


def test_unshared_every_length_equally_nothing_shared():
    t = generator.Traffic(spec.traffic("unshared"), VOCAB, BLOCK, 5)
    assert t.warmup() == [] and t.lengths() == _chat(5).lengths()
    prompts = [t.next() for _ in range(50)]
    assert collections.Counter(len(p.tokens) for p in prompts) == {
        n: 10 for n in (768, 1280, 1792, 2304, 2816)}
    assert all(p.shared == 0 for p in prompts)


def test_rehearsal_scale_keeps_whole_blocks():
    t = _chat(4, scale=16)
    assert t.lengths() == [48, 80, 112, 144, 176]
    with pytest.raises(ValueError):
        generator.Traffic(spec.traffic("chat-shared"), VOCAB, BLOCK, 4, scale=32)
