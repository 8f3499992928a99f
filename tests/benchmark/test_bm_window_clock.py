"""The serving window's clock stands still while the profiler writes its trace
out (PR 34): at a chat cell's rate some request is always in flight when the
window closes, the write-out takes longer than ``drain_s``, and on a clock
that ran on those requests were ``unfinished_after_drain`` in a traced run."""

import types

import pytest

from benchmark.drivers import serving


def window(t0=100.0):
    ctx = types.SimpleNamespace(mix={"trace_s": 6, "drain_s": 30}, rehearse=False)
    w = serving.Window(ctx, engine=None, seconds=51.0, traced=True)
    w.t0 = t0
    return w


def test_an_untraced_window_reads_the_clock_as_it_is():
    w = window()
    assert w.clock(100.0) == 0.0 and w.clock(151.5) == pytest.approx(51.5)


def test_stamps_after_the_write_out_leave_its_length_out():
    w = window()
    w.trace_written = (151.0, 36.0)          # stopped at 51.0 s of the window, 36 s to write
    assert w.clock(150.9) == pytest.approx(50.9)         # delivered before the stop: untouched
    assert w.clock(187.0) == pytest.approx(51.0)         # the write-out's end is its start
    assert w.clock(187.4) == pytest.approx(51.4)         # a token of the drain, 0.4 s after the close
    # the drain's allowance (seconds + drain_s on this clock) is still whole after the write-out
    assert w.clock(187.0) < w.seconds + w.ctx.mix["drain_s"] < w.clock(187.0 + 31.0)
