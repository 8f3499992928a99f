"""Metric arithmetic: percentile rule, failed requests, the clock from due_s."""

import numpy as np
import pytest

from benchmark import metrics


def row(due, stamps, failed=False, prompt=100, want=None):
    return {"due": due, "submitted": due + 0.01, "stamps": list(stamps), "failed": failed,
            "prompt_tokens": prompt, "want": want or len(stamps), "why": None}


@pytest.mark.parametrize("n,q,has", [(99, 90, False), (100, 90, True), (199, 95, False),
                                     (200, 95, True), (1000, 99, True), (999, 99, False)])
def test_percentile_needs_ten_samples_beyond_it(n, q, has):
    got = metrics.percentile_with_room(np.arange(n, dtype=float), q)
    assert (got is not None) == has
    if has:
        assert got == pytest.approx(np.percentile(np.arange(n), q))


def test_ttft_counts_from_due_not_from_submit():
    r = row(due=2.0, stamps=[2.5, 2.5, 2.9])
    r["submitted"] = 2.4                     # the loop was busy: the wait is the system's
    assert metrics.ttft_ms(r) == pytest.approx(500.0)
    assert metrics.tpot_ms(r) == pytest.approx(200.0)     # (2.9 - 2.5) / (3 - 1)
    assert metrics.tpot_ms(row(0, [1.0])) is None


def test_delivery_gaps_skip_tokens_of_one_fetch():
    gaps = metrics.delivery_gaps_ms([row(0, [1.0, 1.0, 1.0, 1.3, 1.3, 1.8])])
    assert gaps == pytest.approx([300.0, 500.0])


def test_failed_requests_miss_every_limit_and_leave_the_medians():
    limits = {"ttft_ms": 1000, "tpot_ms": 100}
    rows = [row(0, [0.5, 0.55, 0.6]),                       # meets both
            row(0, [1.5, 1.55, 1.6]),                       # misses ttft
            row(0, [0.5, 0.9, 1.3]),                        # misses tpot
            row(0, [0.1, 0.15], failed=True),               # failed: misses, whatever its stamps
            row(0, [], failed=True)]
    assert metrics.slo_attainment(rows, limits) == pytest.approx(1 / 5)
    e2e = metrics.serving_end_to_end(rows, seconds=10)
    assert e2e["ttft_ms_p50"] == pytest.approx(500.0)       # over the three good ones
    assert e2e["ttft_ms_p90"] is None                       # far too few samples


def test_tokens_per_s_counts_whole_groups_completed_inside_the_window():
    rows = [row(0, [1.0], prompt=400), row(0, [2.0], prompt=300), row(0, [5.5], prompt=500)]
    e2e = metrics.serving_end_to_end(rows, seconds=5.0)
    assert e2e["tokens_per_s"] == pytest.approx(700 / 2.0)  # the third finished after the close


def test_tpot_median_is_over_the_good_requests_of_two_tokens_or_more():
    """The judged time per token: the median of the per-request values. A
    failed request and a one-token answer (no gap to time) give none."""
    rows = [row(0, [1.0, 1.1, 1.2]),                        # 100 ms a token
            row(0, [1.0, 1.0, 1.0, 1.9]),                   # 300 ms: 0.9 s over 3 gaps
            row(0, [2.0]),                                  # one token: no time per token
            row(0, [0.1, 5.0], failed=True),                # failed: left out
            row(0, [], failed=True)]
    e2e = metrics.serving_end_to_end(rows, seconds=10)
    assert e2e["tpot_ms_p50"] == pytest.approx(200.0)
    rows.append(row(0, [1.0, 1.8]))                         # 800 ms: the median moves to the middle one
    assert metrics.serving_end_to_end(rows, seconds=10)["tpot_ms_p50"] == pytest.approx(300.0)
    none = metrics.serving_end_to_end([row(0, [1.0]), row(0, [], failed=True)], seconds=10)
    assert none["tpot_ms_p50"] is None and "tpot_ms_mean" not in none
