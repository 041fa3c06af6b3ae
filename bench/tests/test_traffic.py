"""The generator offers every seed the same lengths in the same order."""

import numpy as np

from bench import traffic


def test_same_lengths_for_every_seed_and_fit():
    mix = traffic.load_mix("chat")
    pairs = traffic.length_pairs(mix, 32, 1024, 4096)
    assert len(pairs) == 32
    g = traffic.granule(1024)
    assert g == 64
    for p, o in pairs:
        assert p % g == 0 and p + o + 1 <= 4096 and o >= 1
    a = traffic.RequestStream(pairs, 100, 5).next_batch()
    b = traffic.RequestStream(pairs, 100, 2 ** 40 + 5).next_batch()
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in b]
    assert [(len(p), o) for p, o in a] == pairs
    assert any(not np.array_equal(pa, pb) for (pa, _), (pb, _) in zip(a, b))


def test_seed_repeats_its_requests():
    pairs = traffic.length_pairs(traffic.load_mix("repo"), 8, 1176, 16384)
    a = traffic.RequestStream(pairs, 32256, 99).next_batch()
    b = traffic.RequestStream(pairs, 32256, 99).next_batch()
    for (pa, oa), (pb, ob) in zip(a, b):
        assert oa == ob and np.array_equal(pa, pb)


def test_strata_follow_the_mix():
    mix = traffic.load_mix("longdoc")
    pairs = traffic.length_pairs(mix, 8, 1024, 4096)
    prompts = sorted(p for p, _ in pairs)
    assert mix["prompt"]["min"] <= prompts[0] and prompts[-1] <= 3840
    assert abs(prompts[3] - mix["prompt"]["median"]) <= 256
    assert all(16 <= o <= 64 for _, o in pairs)


def test_granule_divides_the_page():
    assert traffic.granule(1176) == 56
    assert traffic.granule(688) == 43
