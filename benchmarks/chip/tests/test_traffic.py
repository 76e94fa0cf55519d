import numpy as np
import pytest

from harness import spec, traffic
from harness.warmup import zero_page_counts

BIG_SEED = 2**31 + 12345
# a chat-like mix as a later cell's data file would hold it
CHAT = {"loop": "closed", "requests": 40, "size_seed": 11,
        "prompt_bands": [[128, 0.35], [256, 0.3], [512, 0.2], [1024, 0.15]],
        "output": [16, 128]}
MIXES = ["decode_2k", "chat"]


def load(name):
    if name == "chat":
        return dict(CHAT)
    mix = spec.load_json(spec.BENCH_DIR / "traffic" / f"{name}.json")
    return {k: v for k, v in mix.items() if k != "rehearse"}


def gen(mix, seed, clients=8):
    return traffic.generate(mix, seed, vocab=50_000, chunk=64, clients=clients)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = load(name)
    a, b = gen(mix, BIG_SEED), gen(mix, BIG_SEED)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert (x.rid, x.client, x.output) == (y.rid, y.client, y.output)
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_work_other_order(name):
    mix = load(name)
    a, b = gen(mix, 1), gen(mix, BIG_SEED)
    sizes = lambda rs: sorted((len(r.prompt), r.output) for r in rs)
    assert sizes(a) == sizes(b)
    assert any(not np.array_equal(x.prompt[:8], y.prompt[:8]) for x, y in zip(a, b))
    assert [r.client for r in a] != [r.client for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_band_lengths_never_exact_multiples(name):
    mix = load(name)
    reqs = gen(mix, 7)
    bands = [b for b, _ in mix["prompt_bands"]]
    for r in reqs:
        n = len(r.prompt)
        assert n % 64 != 0
        band = -(-n // 64) * 64
        assert band in bands and band - 64 < n < band
        assert mix["output"][0] <= r.output <= mix["output"][1] or (
            mix.get("first_output_from_one") and r.output >= 1)
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 50_000


@pytest.mark.parametrize("name", MIXES)
def test_band_weights_followed(name):
    mix = load(name)
    reqs = gen(mix, 3, clients=64)
    counts = {}
    for r in reqs:
        b = -(-len(r.prompt) // 64) * 64
        counts[b] = counts.get(b, 0) + 1
    for band, w in mix["prompt_bands"]:
        assert abs(counts[band] / len(reqs) - w) < 0.01


def test_closed_loop_streams_and_first_outputs():
    mix = load("decode_2k")
    reqs = gen(mix, 5)
    per = mix["requests"]
    assert len(reqs) == 8 * per
    for c in range(8):
        mine = [r for r in reqs if r.client == c]
        assert len(mine) == per
    firsts = [reqs[i * per] for i in range(8)]
    assert all(1 <= r.output <= mix["output"][1] for r in firsts)
    assert any(r.output < mix["output"][0] for r in firsts)


def test_open_loop_has_no_generator():
    with pytest.raises(ValueError, match="open"):
        gen(dict(CHAT, loop="open"), 9)


def test_max_context_and_zero_pages():
    mix = load("decode_2k")
    assert traffic.max_context(mix) == 1983 + 63
    # a 1024-band prompt (15 closed chunks) with 1..64 output tokens
    # reserves 1 or 2 zero pages past its closed chunks
    assert list(zero_page_counts(mix, 1024, 64)) == [1, 2]
    chat = load("chat")
    assert list(zero_page_counts(chat, 128, 64)) == [1, 2, 3]
