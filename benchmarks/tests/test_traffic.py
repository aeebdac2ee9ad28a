"""The generator: deterministic in the seed, inside its bounds, and the
same work for every seed."""

import pytest

import traffic

MIXES = ["rag-open", "rag-closed", "chat-closed"]
VOCAB = 32768


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load_mix(name)
    a = traffic.generate(mix, 2**31 + 7, VOCAB, 300, 2.5)
    b = traffic.generate(mix, 2**31 + 7, VOCAB, 300, 2.5)
    assert a == b
    assert traffic.warmup_bursts(mix, 5, VOCAB) == traffic.warmup_bursts(mix, 5, VOCAB)
    assert traffic.reference_prompts(mix, 5, VOCAB, 8) == traffic.reference_prompts(mix, 5, VOCAB, 8)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_shapes_and_arrivals(name):
    mix = traffic.load_mix(name)
    a = traffic.generate(mix, 1, VOCAB, 400, 2.5)
    b = traffic.generate(mix, 2**31 + 99, VOCAB, 400, 2.5)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]  # other tokens

    def shape(r):
        return (len(r["prompt"]), r["max_tokens"], r["due"])

    assert list(map(shape, a)) == list(map(shape, b))  # in the same order


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_inside_their_bounds(name):
    mix = traffic.load_mix(name)
    reqs = traffic.generate(mix, 3, VOCAB, 2 * int(mix["spec_requests"]), 0.0)
    prefix = int(mix["prefix_tokens"])
    docs = mix.get("docs")
    lo = prefix + mix["unique"]["lo"] + (docs["per_request"] * docs["len"]["lo"] if docs else 0)
    hi = prefix + mix["unique"]["hi"] + (docs["per_request"] * docs["len"]["hi"] if docs else 0)
    for r in reqs:
        assert lo <= len(r["prompt"]) <= hi
        assert mix["max_tokens"]["lo"] <= r["max_tokens"] <= mix["max_tokens"]["hi"]
        assert len(r["prompt"]) + r["max_tokens"] <= mix["max_total"]
        assert min(r["prompt"]) >= mix["vocab_floor"] and max(r["prompt"]) < VOCAB
    # Nothing is clipped: max_len 2048 less the decode chunk's flush margin.
    assert int(mix["max_total"]) < 2048 - 9
    for burst in traffic.warmup_bursts(mix, 3, VOCAB):
        for r in burst:
            assert len(r["prompt"]) + r["max_tokens"] < 2048 - 9


def test_rag_shares_what_it_says():
    mix = traffic.load_mix("rag-open")
    reqs = traffic.generate(mix, 11, VOCAB, 240, 1.0)
    prefix = int(mix["prefix_tokens"])
    assert len({tuple(r["prompt"][:prefix]) for r in reqs}) == 1  # one template
    shapes = traffic.request_shapes(mix)
    repeats = sum(
        1 for i in range(1, 240)
        if any(shapes["picks"][i] == shapes["picks"][j] for j in range(max(0, i - 8), i))
    )
    assert 0.1 < repeats / 240 < 0.3  # about a fifth ask again
    first = [p[0] for p in shapes["picks"]]
    assert first.count(0) / 240 > 0.1  # Zipf: the top chunk leads often


def test_chat_shares_a_line_too_short_to_hit():
    mix = traffic.load_mix("chat-closed")
    assert mix["arrivals"] == {"loop": "closed", "clients": 48}
    assert mix["prefix_tokens"] == 24 and "docs" not in mix  # under MIN_PREFIX 32
    reqs = traffic.generate(mix, 2**31 + 5, VOCAB, 512)
    assert len({tuple(r["prompt"][:24]) for r in reqs}) == 1
    assert len({tuple(r["prompt"][:25]) for r in reqs}) > 1  # and nothing after it
    lens = sorted(len(r["prompt"]) - 24 for r in reqs)
    outs = sorted(r["max_tokens"] for r in reqs)
    assert 80 < lens[256] < 115 and 160 < outs[256] < 230  # medians 96 and 192
    # What the warm-up plan leaves out: no request reaches the window of 2,048.
    assert max(len(r["prompt"]) + r["max_tokens"] for r in reqs) + 9 <= 1024
    lo, hi = mix["reference_len"]
    assert lo <= 256 < hi <= 288  # the reference check meets both admission paths


def test_open_loop_rate():
    mix = traffic.load_mix("rag-open")
    reqs = traffic.generate(mix, 1, VOCAB, 240, 2.0)
    assert reqs[-1]["due"] == pytest.approx(240 / 2.0, rel=0.15)
    assert all(b["due"] > a["due"] for a, b in zip(reqs, reqs[1:]))


@pytest.mark.parametrize(
    "dist",
    [
        {"dist": "uniform", "lo": 16, "hi": 64},
        {"dist": "lognormal", "median": 96, "sigma": 0.8, "lo": 16, "hi": 512},
        {"dist": "fixed", "value": 128, "lo": 1, "hi": 4096},
    ],
    ids=lambda d: d["dist"],
)
def test_every_length_distribution_keeps_its_bounds(dist):
    import numpy as np

    xs = traffic._draw(np.random.default_rng(4), dist, 2000)
    assert xs.min() >= dist["lo"] and xs.max() <= dist["hi"]
    if dist["dist"] == "lognormal":
        assert 0.8 * dist["median"] < np.median(xs) < 1.25 * dist["median"]
    with pytest.raises(ValueError):
        traffic._draw(np.random.default_rng(4), {**dist, "dist": "zipf"}, 1)
