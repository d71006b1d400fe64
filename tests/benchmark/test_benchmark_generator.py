"""The load generators: the same for a seed, another order for another seed,
the same work for every seed."""

import json
import os

import numpy as np
import pytest

from benchmarks.generators import open_loop, packed_tokens

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHAT = json.load(open(os.path.join(ROOT, "benchmarks", "traffic", "chat.json")))
BIG = 3_000_000_019    # more than 32 signed bits hold, as the driver's seeds


def _gen(seed, rate=8.0, seconds=30.0):
    return open_loop.generate(CHAT, rate, seconds, seed, 32000)


def test_same_seed_same_traffic():
    a, b = _gen(BIG), _gen(BIG)
    assert [(x.due, x.prompt, x.max_new_tokens) for x in a] == \
        [(x.due, x.prompt, x.max_new_tokens) for x in b]


def test_another_seed_same_work_in_another_order():
    a, b = _gen(BIG), _gen(BIG + 1)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new_tokens for x in a) == \
        sorted(x.max_new_tokens for x in b)
    assert a[0].prompt != b[0].prompt
    inside = lambda xs: [x for x in xs if x.due >= 0]
    assert len(inside(a)) == 240 and len(a) == 240 + 8 * CHAT["lead_seconds"]
    # the requests due inside the window are the same set for every seed
    assert sorted(len(x.prompt) for x in inside(a)) == \
        sorted(len(x.prompt) for x in inside(b))
    assert sum(x.max_new_tokens for x in inside(a)) == \
        sum(x.max_new_tokens for x in inside(b))
    gaps = lambda xs: set(np.round(np.diff([x.due for x in inside(xs)]), 9))
    assert len(gaps(a) ^ gaps(b)) <= 2     # all but the gap each left first


def test_rate_span_and_limits():
    xs = _gen(7, rate=8.0, seconds=30.0)
    lead = CHAT["lead_seconds"]
    assert len(xs) == round(8.0 * 30) + round(8.0 * lead)
    due = [x.due for x in xs]
    assert [x.due for x in xs if x.due >= 0][0] == 0.0
    assert due == sorted(due) and due[0] == -lead and due[-1] < 30
    p, o = CHAT["prompt_tokens"], CHAT["output_tokens"]
    assert all(p["min"] <= len(x.prompt) <= p["max"] for x in xs)
    assert all(o["min"] <= x.max_new_tokens <= o["max"] for x in xs)
    assert abs(np.median([len(x.prompt) for x in xs]) - p["median"]) < 8
    assert all(0 < t < 32000 for x in xs[:5] for t in x.prompt)


def test_arrival_gaps_are_the_exponential_quantiles():
    arr = CHAT["arrivals"]
    assert arr == {"process": "stratified_exponential"}    # named for what it is
    g = np.array([open_loop._gap_quantile(arr, (i + 0.5) / 4000)
                  for i in range(4000)])
    assert abs(g.mean() - 1.0) < 0.05
    assert abs(g.std() / g.mean() - 1.0) < 0.25


@pytest.mark.parametrize("mix", [
    dict(CHAT, arrivals={"process": "poisson"}),
    dict(CHAT, prompt_tokens={"dist": "loguniform", "min": 8, "max": 64})],
    ids=["process", "dist"])
def test_a_mix_the_generator_does_not_know_is_an_error(mix):
    with pytest.raises(ValueError, match="unknown"):
        open_loop.generate(mix, 2.0, 20.0, 5, 32000)


def test_the_by_hand_independent_draw_varies_in_count_and_follows_its_seed():
    from benchmarks import sweep

    draw = lambda seed: sweep.iid_arrivals(open_loop, CHAT, 8.0, 30.0, seed, 32000)
    a, b = draw(BIG), draw(BIG)
    assert [(x.due, x.prompt) for x in a] == [(x.due, x.prompt) for x in b]
    counts = {len(draw(s)) for s in range(8)}
    assert len(counts) > 1 and all(200 < n < 380 for n in counts)
    lead = CHAT["lead_seconds"]
    assert all(-lead < x.due < 30 for x in a)
    p = CHAT["prompt_tokens"]
    assert all(p["min"] <= len(x.prompt) <= p["max"] for x in a)


def test_packed_batches_follow_seed_and_step():
    mix = {"global_batch": 2, "seq_len": 64}
    a = packed_tokens.batch(mix, BIG, 3, 32000)
    assert a.shape == (2, 64) and a.dtype == np.int32
    assert (a == packed_tokens.batch(mix, BIG, 3, 32000)).all()
    assert (a != packed_tokens.batch(mix, BIG, 4, 32000)).any()
    assert (a != packed_tokens.batch(mix, BIG + 1, 3, 32000)).any()
