"""The generator: every seed does the same work."""

import json
import os

import pytest

from benchmarks.lib import traffic as T

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = ["decode-closed", "prefill-open"]
SEEDS = [0, 1, 7, 2 ** 31 + 11, 2 ** 32 + 5]


def mix(name, fixed_order=False):
    """A committed mix; without its ``order_seed`` unless asked, so that
    the generator's own rule (the seed permutes) is what is tested."""
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        m = json.load(f)
    if not fixed_order:
        m.pop("order_seed", None)
    return m


@pytest.mark.parametrize("name", MIXES)
def test_a_fixed_order_replays_the_same_lengths_with_other_tokens(name):
    m = mix(name, fixed_order=True)
    assert "order_seed" in m
    a, b = T.Requests(m, 1, 32000), T.Requests(m, 2 ** 31 + 5, 32000)
    assert a.prompt_len.tolist() == b.prompt_len.tolist()
    assert a.answer_len.tolist() == b.answer_len.tolist()
    assert a.gap_s.tolist() == b.gap_s.tolist()
    assert a.phase.tolist() == b.phase.tolist()
    assert a.tokens(0, 64) != b.tokens(0, 64)
    assert a.multisets() == T.Requests(mix(name), 3, 32000).multisets()


@pytest.mark.parametrize("name", MIXES)
def test_same_multisets_for_every_seed_in_another_order(name):
    m = mix(name)
    base = T.Requests(m, SEEDS[0], 32000)
    for seed in SEEDS[1:]:
        other = T.Requests(m, seed, 32000)
        assert other.multisets() == base.multisets()
        assert other.prompt_len.tolist() != base.prompt_len.tolist()
        assert other.answer_len.tolist() != base.answer_len.tolist()
    # the pairing of prompt and answer lengths is the seed's too
    a, b = T.Requests(m, 1, 32000), T.Requests(m, 2, 32000)
    assert sorted(zip(a.prompt_len, a.answer_len)) != \
        sorted(zip(b.prompt_len, b.answer_len))


def test_grids_are_quantile_midpoints():
    g = T.grid({"dist": "uniform", "lo": 0.0, "hi": 8.0}, 4)
    assert g.tolist() == [1.0, 3.0, 5.0, 7.0]
    g = T.grid({"dist": "loguniform", "lo": 1.0, "hi": 16.0}, 2)
    assert g.tolist() == pytest.approx([2.0, 8.0])
    g = T.grid({"dist": "exponential", "mean": 2.0}, 1000)
    assert g.mean() == pytest.approx(2.0, rel=0.01)
    assert T.grid({"dist": "const", "value": 5}, 3).tolist() == [5.0] * 3
    with pytest.raises(ValueError):
        T.grid({"dist": "zipf"}, 3)


def test_lengths_stay_inside_the_declared_range():
    m = mix("decode-closed")
    r = T.Requests(m, 3, 32000)
    assert r.prompt_len.min() >= m["prompt_tokens"]["lo"]
    assert r.prompt_len.max() <= m["prompt_tokens"]["hi"]
    assert r.answer_len.min() >= m["answer_tokens"]["lo"]
    assert r.answer_len.max() <= m["answer_tokens"]["hi"]
    assert r.answer_len.mean() == pytest.approx(256, abs=1)


def test_open_loop_offers_its_rate():
    m = mix("prefill-open")
    r = T.Requests(m, 5, 32000)
    assert (r.gap_s > 0).all()
    # quantile midpoints cut the exponential's tail: a little under 1/rate
    assert r.n / r.gap_s.sum() == pytest.approx(m["rate_per_s"], rel=0.03)


def test_bursts_arrive_together_at_the_same_rate():
    m = dict(mix("prefill-open"), burst=4)
    r = T.Requests(m, 5, 32000)
    assert (r.gap_s[0::4] == 0).all() and (r.gap_s[3::4] > 0).all()
    assert r.n / r.gap_s.sum() == pytest.approx(m["rate_per_s"], rel=0.1)


def test_closed_loop_starts_in_steady_state():
    m = mix("decode-closed")
    r = T.Requests(m, 9, 32000)
    first = T.closed_first_round(r, m["clients"])
    for c, e in enumerate(first):
        whole = r.entry(c)
        assert 1 <= e["answer_len"] <= whole["answer_len"]
        # context at the end is that of the whole request
        assert e["prompt_len"] + e["answer_len"] == \
            whole["prompt_len"] + whole["answer_len"]
    total = sum(e["answer_len"] for e in first)
    # remaining lengths are spread evenly: about half of each answer is left
    assert total == pytest.approx(0.5 * r.answer_len.sum(), rel=0.1)
    # the same phases for every seed
    assert sorted(T.Requests(m, 10, 32000).phase) == sorted(r.phase)


def test_token_ids_are_the_seeds_and_never_repeat_with_the_cycle():
    m = mix("decode-closed")
    r = T.Requests(m, 4, 32000)
    assert r.tokens(3, 50) == T.Requests(m, 4, 32000).tokens(3, 50)
    assert r.tokens(3, 50) != T.Requests(m, 5, 32000).tokens(3, 50)
    assert r.tokens(3, 50) != r.tokens(3 + r.n, 50)       # same length slot
    assert all(0 <= t < 32000 for t in r.tokens(1, 200))


def test_shared_prefix_share():
    m = dict(mix("decode-closed"),
             shared_prefix={"share": 0.5, "tokens": 32, "prefixes": 2})
    r = T.Requests(m, 4, 32000)
    heads = [tuple(r.tokens(k, 64)[:32]) for k in range(r.n)]
    shared = sum(1 for h in heads if list(h) in r.prefixes)
    assert shared == r.n // 2


def test_token_stream_is_seeded():
    a = T.token_stream(2 ** 31 + 3, 50304, 4096)
    assert (a == T.token_stream(2 ** 31 + 3, 50304, 4096)).all()
    assert (a != T.token_stream(2 ** 31 + 4, 50304, 4096)).any()
    assert a.min() >= 0 and a.max() < 50304
