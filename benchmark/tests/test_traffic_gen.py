"""The generator: the same seed gives the same inputs, another seed other
token ids on the same schedule, in every arrival kind."""

import json
import os

import numpy as np
import pytest

from benchmark import traffic_gen

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(os.path.dirname(HERE), "traffic",
                       "chat-closed.json")) as f:
    CHAT = json.load(f)
ARRIVALS = {
    "closed": {"kind": "closed", "clients": 32},
    "poisson": {"kind": "poisson", "rate_rps": 3.0},
    "gamma": {"kind": "gamma", "rate_rps": 3.0, "cv": 2.5},
}


def schedule(kind, seed, n=96):
    traffic = dict(CHAT, arrivals=ARRIVALS[kind])
    return traffic_gen.serve_requests(traffic, seed, n, 50272)


def same(a, b):
    return all(np.array_equal(x["prompt"], y["prompt"])
               and x["max_new_tokens"] == y["max_new_tokens"]
               and x["due_s"] == y["due_s"] for x, y in zip(a, b))


@pytest.mark.parametrize("kind", sorted(ARRIVALS))
def test_same_seed_same_schedule_other_seed_other(kind):
    big = 2 ** 31 + 351
    assert same(schedule(kind, big), schedule(kind, big))
    assert not same(schedule(kind, big), schedule(kind, 7))


@pytest.mark.parametrize("kind", sorted(ARRIVALS))
def test_every_seed_gets_the_same_schedule_and_every_round_the_same_sizes(
        kind):
    k = CHAT["round_size"]
    sizes = lambda s: [(len(r["prompt"]), r["max_new_tokens"], r["due_s"])
                       for r in s]
    a, b = schedule(kind, 1), schedule(kind, 2)
    assert sizes(a) == sizes(b)
    rounds = [sorted(x[:2] for x in sizes(a)[i:i + k])
              for i in range(0, 96, k)]
    assert rounds[0] == rounds[1] == rounds[2]
    assert sizes(a)[:k] != sizes(a)[k:2 * k]     # in another order


def test_another_schedule_seed_gives_another_order():
    other = traffic_gen.serve_requests(
        dict(CHAT, schedule_seed=1), 1, 64, 50272)
    assert [len(r["prompt"]) for r in other] \
        != [len(r["prompt"]) for r in schedule("closed", 1, 64)]


def test_lengths_follow_the_clipped_lognormal():
    prompts = traffic_gen.lognormal_quantiles(CHAT["prompt_len"], 32)
    lo, hi = CHAT["prompt_len"]["clip"]
    assert prompts.min() >= lo and prompts.max() == hi
    assert abs(np.median(prompts) - CHAT["prompt_len"]["median"]) < 20
    outs = traffic_gen.lognormal_quantiles(CHAT["output_len"], 32)
    assert outs.min() >= 8 and outs.max() <= 256


def test_closed_loop_has_no_due_times():
    assert all(r["due_s"] is None for r in schedule("closed", 3))


@pytest.mark.parametrize("kind", ["poisson", "gamma"])
def test_open_loop_rounds_last_exactly_round_size_over_rate(kind):
    k, rate = CHAT["round_size"], ARRIVALS[kind]["rate_rps"]
    due = [r["due_s"] for r in schedule(kind, 5)]
    assert due == sorted(due) and due[0] > 0
    for i in (1, 2, 3):
        assert due[i * k - 1] == pytest.approx(i * k / rate)


def test_gamma_arrivals_are_burstier_than_poisson():
    cv = lambda kind: np.std(np.diff([r["due_s"] for r in schedule(
        kind, 11, 960)])) / np.mean(np.diff([r["due_s"] for r in schedule(
            kind, 11, 960)]))
    assert cv("gamma") > 1.5 * cv("poisson")


def test_train_batches_are_seeded_and_zipf():
    traffic = {"zipf_exponent": 1.2}
    take = lambda seed: next(traffic_gen.train_batches(
        traffic, seed, 4, 64, 512))["input_ids"]
    a = take(2 ** 31 + 5)
    assert a.shape == (4, 64) and a.dtype == np.int32
    assert np.array_equal(a, take(2 ** 31 + 5))
    assert not np.array_equal(a, take(6))
    assert 0 <= a.min() and a.max() < 512
    assert (a == 0).mean() > 0.1     # rank 1 is the commonest token
