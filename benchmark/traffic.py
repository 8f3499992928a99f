"""The one traffic generator: a mix is a data file, this reads it.

``traffic/<mix>.json`` holds parameters only (lengths, rate, arrival process,
bursts, sharing, clients); a new mix is a new file and no code. Everything is
drawn from ``--seed`` before the window opens, and the program sees only the
generated requests.

Steadiness: every draw is STRATIFIED. A run of n requests takes the n
quantiles ``(i + 0.5) / n`` of each stated distribution (prompt lengths,
answer lengths, the exponential gaps of a Poisson process), so every run
offers the same multiset of lengths and gaps: the same amount of work over the
same span. An open-loop window also offers them in the same ORDER whatever the
seed: one permutation of the gaps and of the lengths, drawn from
``ORDER_SEED``; ``--seed`` draws the token ids here and the weights in the
driver. An open loop at four fifths of its knee is not steady under a
reordering of the same work: six seeds' orders moved a window's time per
token by 4 % in ``olmoe-1b-7b.chat``, median and mean alike, against a bound
of 4 % (PERF.md section 6, PR 34). ``ORDER_SEED`` is a seed of PR 34's rate
sweeps, so the knees the mixes record were read under this very order. A
closed loop's stream is ordered by the seed: a saturated system has no queue
for the order to move.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent

# orders every open-loop window (see above); 2147490101 is a seed of PR 34's sweeps
ORDER_SEED = 2147490101


@dataclasses.dataclass
class Req:
    due_s: float                # seconds after the window opens
    prompt: np.ndarray          # (s,) int32, ids in [1, vocab)
    max_new_tokens: int


def load_mix(name: str, rehearse: bool = False, root: Path = HERE) -> dict:
    mix = json.loads((root / "traffic" / f"{name}.json").read_text())
    if rehearse:
        mix.update(mix.get("rehearsal") or {})
    mix["name"] = name
    return mix


# ------------------------------------------------------------ distributions

def _norm_ppf(q: np.ndarray) -> np.ndarray:
    inv = NormalDist().inv_cdf
    return np.asarray([inv(float(x)) for x in np.ravel(q)]).reshape(np.shape(q))


def quantiles(dist: dict, q: np.ndarray) -> np.ndarray:
    """Integer lengths at quantiles ``q`` of one distribution entry."""
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full(q.shape, float(dist["value"]))
    elif kind == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * _norm_ppf(q))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    x = np.rint(x)
    if "min" in dist:
        x = np.maximum(x, dist["min"])
    if "max" in dist:
        x = np.minimum(x, dist["max"])
    return x.astype(np.int64)


def stratified_lengths(mixture: List[dict], n: int, rng: np.random.RandomState) -> np.ndarray:
    """n lengths: each mixture component gives its weight's share of n, at
    the quantiles of its own distribution; the seed permutes the whole."""
    weights = np.asarray([c.get("weight", 1.0) for c in mixture], np.float64)
    counts = np.floor(weights / weights.sum() * n).astype(int)
    counts[: n - counts.sum()] += 1          # largest-first remainder
    parts = [quantiles(c, (np.arange(k) + 0.5) / k) for c, k in zip(mixture, counts) if k]
    out = np.concatenate(parts) if parts else np.zeros((0,), np.int64)
    return out[rng.permutation(out.size)]


def arrival_times(arrivals: dict, rate_per_s: float, seconds: float,
                  rng: np.random.RandomState) -> np.ndarray:
    """Due times in [0, seconds) of ``round(rate * seconds)`` requests.

    ``poisson``: the gaps are the stratified quantiles of the exponential
    distribution in a seeded order, rescaled to span the window.
    ``burst`` (optional: ``{"period_s", "duty", "factor"}``) warps that
    process so that for ``duty`` of every period the rate is ``factor``
    times what it is in the rest, at the same mean rate.
    """
    n = int(round(rate_per_s * seconds))
    if n <= 0:
        return np.zeros((0,))
    if arrivals.get("process", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    gaps = gaps[rng.permutation(n)]
    # the first request is due as the window opens; the same scale for every
    # seed, so that every seed offers the same gaps
    t = (np.cumsum(gaps) - gaps[0]) * (seconds / gaps.sum())
    burst = arrivals.get("burst")
    if burst:
        period, duty, factor = burst["period_s"], burst["duty"], burst["factor"]
        low = 1.0 / (duty * factor + (1 - duty))     # rate multipliers, mean 1
        high = factor * low
        k, w = np.divmod(t, period)                  # w: work done within the period
        on_work = high * duty * period
        inside = np.where(w < on_work, w / high, duty * period + (w - on_work) / low)
        t = k * period + inside
    return t


# ----------------------------------------------------------------- requests

def _prompts(mix: dict, lengths: np.ndarray, vocab: int,
             rng: np.random.RandomState) -> List[np.ndarray]:
    shared = mix.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = [rng.randint(1, vocab, (int(shared["tokens"]),)).astype(np.int32)
                    for _ in range(int(shared.get("groups", 1)))]
    out = []
    for i, n in enumerate(lengths):
        ids = rng.randint(1, vocab, (int(n),)).astype(np.int32)
        if prefixes is not None:
            p = prefixes[i % len(prefixes)][: max(int(n) - 1, 0)]
            ids[: p.size] = p
        out.append(ids)
    return out


def open_loop(mix: dict, vocab: int, seed: int, seconds: float,
              rate_per_s: Optional[float] = None) -> List[Req]:
    """Every request of an open-loop window, sorted by due time: gaps and
    lengths in ``ORDER_SEED``'s order, token ids from ``seed``."""
    order = np.random.RandomState(ORDER_SEED)
    due = arrival_times(mix.get("arrivals") or {}, rate_per_s or mix["rate_per_s"],
                        seconds, order)
    n = due.size
    plen = stratified_lengths(mix["prompt_tokens"], n, order)
    alen = stratified_lengths(mix["answer_tokens"], n, order)
    prompts = _prompts(mix, plen, vocab, np.random.RandomState(seed))
    return [Req(float(due[i]), prompts[i], int(alen[i])) for i in range(n)]


def closed_loop(mix: dict, vocab: int, seed: int, block: int = 64) -> Iterator[Req]:
    """An endless seeded stream for a closed loop (``due_s`` is set by the
    driver when a client is free): stratified blocks of ``block`` requests."""
    rng = np.random.RandomState(seed)
    while True:
        plen = stratified_lengths(mix["prompt_tokens"], block, rng)
        alen = stratified_lengths(mix["answer_tokens"], block, rng)
        for p, a in zip(_prompts(mix, plen, vocab, rng), alen):
            yield Req(math.nan, p, int(a))


def length_range(mixture: List[dict]) -> tuple:
    """(smallest, largest) length the mixture can produce."""
    lo = min(int(quantiles(c, np.asarray([1e-9]))[0]) for c in mixture)
    hi = max(int(quantiles(c, np.asarray([1 - 1e-9]))[0]) for c in mixture)
    return lo, hi


def train_batches(mix: dict, vocab: int, seed: int) -> Iterator[dict]:
    """Seeded next-token batches: ``labels[i]`` is the target of position i."""
    rng = np.random.RandomState(seed)
    b, s = int(mix["global_batch"]), int(mix["seq_len"])
    while True:
        ids = rng.randint(0, vocab, (b, s + 1)).astype(np.int32)
        yield {"ids": ids[:, :-1], "labels": ids[:, 1:]}
