"""The one traffic generator: seeded, stratified, driven by the mix file."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import traffic

MIXES = sorted(p.stem for p in (Path(traffic.HERE) / "traffic").glob("*.json"))


def test_same_seed_same_requests_other_seed_same_order_other_tokens():
    mix = traffic.load_mix("chat-short")
    a = traffic.open_loop(mix, 32000, 3, 45)
    b = traffic.open_loop(mix, 32000, 3, 45)
    c = traffic.open_loop(mix, 32000, 4, 45)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() and x.max_new_tokens == y.max_new_tokens
               for x, y in zip(a, b))
    # another seed: the same lengths and gaps in the same order (PR 34), other token ids
    assert [r.due_s for r in a] == [r.due_s for r in c]
    assert [r.prompt.size for r in a] == [r.prompt.size for r in c]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in c]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, c))
    # the n - 1 gaps are the n stratified gaps less the one the order put first
    n = len(a)
    every = set(np.round(-np.log(1 - (np.arange(n) + 0.5) / n) * 45 / n
                         / np.mean(-np.log(1 - (np.arange(n) + 0.5) / n)), 9))
    assert len(every - set(np.round(np.diff([r.due_s for r in a]), 9))) == 1


OPEN = [m for m in MIXES if traffic.load_mix(m).get("loop") == "open"]


@pytest.mark.parametrize("name", OPEN)
def test_an_open_loop_mix_gives_every_seed_the_same_trace_and_other_tokens(name, monkeypatch):
    """Since PR 34 ``traffic.ORDER_SEED`` orders the gaps and lengths of every
    open-loop window: ``--seed`` draws the token ids (and the weights) only,
    and no mix carries a knob for it."""
    mix = traffic.load_mix(name)
    assert "order" not in mix
    a = traffic.open_loop(mix, 32000, 3, 51)
    c = traffic.open_loop(mix, 32000, 2147492001, 51)
    assert [r.due_s for r in a] == [r.due_s for r in c]
    assert [r.prompt.size for r in a] == [r.prompt.size for r in c]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in c]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, c))
    # another order seed: the same multiset in another order
    monkeypatch.setattr(traffic, "ORDER_SEED", traffic.ORDER_SEED + 1)
    other = traffic.open_loop(mix, 32000, 3, 51)
    assert [r.max_new_tokens for r in a] != [r.max_new_tokens for r in other]
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in other)
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size for r in other)


@pytest.mark.parametrize("name,rate", [("chat-short", 4.5), ("chat-short-olmoe", 4.0),
                                       ("longctx-decode", 1.6), ("longctx-decode", 1.8)])
def test_the_order_is_the_one_the_sweep_ran_under_its_seed(name, rate):
    """PR 34's sweeps let each seed order its own window; one of their seeds is
    ``ORDER_SEED``. So a window of any ``--seed`` offers, at a swept rate, the
    very gaps and lengths of that sweep window: the knee was read under the
    order the cell runs."""
    mix = traffic.load_mix(name)
    rng = np.random.RandomState(2147490101)         # as the sweep's generator drew them
    due = traffic.arrival_times(mix["arrivals"], rate, 51, rng)
    plen = traffic.stratified_lengths(mix["prompt_tokens"], due.size, rng)
    alen = traffic.stratified_lengths(mix["answer_tokens"], due.size, rng)
    got = traffic.open_loop(mix, 32000, 7, 51, rate)
    assert traffic.ORDER_SEED == 2147490101 and len(got) == round(rate * 51)
    assert [r.due_s for r in got] == list(due)
    assert [r.prompt.size for r in got] == list(plen)
    assert [r.max_new_tokens for r in got] == list(alen)


@pytest.mark.parametrize("name", ["chat-short", "longctx-decode"])
def test_open_loop_has_the_stated_rate_and_lengths(name):
    mix = traffic.load_mix(name)
    reqs = traffic.open_loop(mix, 32000, 0, 45)
    assert len(reqs) == round(mix["rate_per_s"] * 45)
    due = np.asarray([r.due_s for r in reqs])
    assert due[0] == 0.0 and (np.diff(due) >= 0).all() and due[-1] < 45
    for key, got in (("prompt_tokens", [r.prompt.size for r in reqs]),
                     ("answer_tokens", [r.max_new_tokens for r in reqs])):
        d = mix[key][0]
        assert d["min"] <= min(got) and max(got) <= d["max"]
        assert abs(np.median(got) - d["median"]) <= 0.05 * d["median"]
    # the gaps are a sample of the exponential distribution: mean = std = 1/rate
    gaps = np.diff(due)
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.15
    assert all(r.prompt.min() >= 1 and r.prompt.max() < 32000 for r in reqs)


def test_burst_keeps_the_mean_rate_and_crowds_the_duty_part():
    rng = np.random.RandomState(0)
    t = traffic.arrival_times({"process": "poisson",
                               "burst": {"period_s": 10, "duty": 0.2, "factor": 5}}, 4.0, 40, rng)
    assert len(t) == 160 and t[-1] < 40
    share_on = np.mean((t % 10) < 2.0)
    assert abs(share_on - 5 * 0.2 / (5 * 0.2 + 0.8)) < 0.05


def test_mixture_and_shared_prefix():
    mix = {"prompt_tokens": [{"weight": 0.9, "dist": "uniform", "min": 50, "max": 100},
                             {"weight": 0.1, "dist": "fixed", "value": 2000}],
           "answer_tokens": [{"dist": "fixed", "value": 8}], "arrivals": {},
           "shared_prefix": {"tokens": 32, "groups": 2}, "rate_per_s": 2.0}
    reqs = traffic.open_loop(mix, 1000, 1, 50)
    sizes = [r.prompt.size for r in reqs]
    assert sizes.count(2000) == 10 and len(sizes) == 100
    heads = {tuple(r.prompt[:32]) for r in reqs}
    assert len(heads) == 2
    assert traffic.length_range(mix["prompt_tokens"]) == (50, 2000)


def test_closed_loop_stream_and_train_batches_are_seeded():
    mix = traffic.load_mix("score-prefill-only")
    a, b = traffic.closed_loop(mix, 32000, 5), traffic.closed_loop(mix, 32000, 5)
    first = [next(a) for _ in range(70)]
    assert all((x.prompt == next(b).prompt).all() for x in first)
    assert all(256 <= x.prompt.size <= 512 and x.max_new_tokens == 1 for x in first)
    tb = traffic.train_batches(traffic.load_mix("train-2k-fixed"), 50432, 1)
    one, two = next(tb), next(tb)
    assert one["ids"].shape == (8, 2048) and (one["ids"][:, 1:] == one["labels"][:, :-1]).all()
    assert (one["ids"] != two["ids"]).any()


@pytest.mark.parametrize("name", MIXES)
def test_every_mix_file_loads_and_names_its_driver(name):
    mix = traffic.load_mix(name)
    assert (Path(traffic.HERE) / "drivers" / f"{mix['driver']}.py").exists()
    assert mix["what"]
    rehearsal = traffic.load_mix(name, rehearse=True)
    assert set(json.dumps(rehearsal)) and rehearsal["driver"] == mix["driver"]
