"""From the profiler's ``.xplane.pb`` to the few numbers the benchmark keeps.

Read with ``jax.profiler.ProfileData`` and nothing else. What a TPU v5e trace
looks like (looked at by hand, PR 22; ``python benchmark/trace_reduce.py
<file> --dump`` prints the same for any trace):

* one plane per chip, ``/device:TPU:<n>`` (beside empty ``#Chip<n> ...``
  planes); its line ``XLA Ops`` holds one event per executed HLO op, whose
  NAME IS THE WHOLE INSTRUCTION TEXT (``%fusion.12 = bf16[8,4096]{...}
  fusion(...)``, ``%all-reduce.7 = ...``, ``%custom-call.3 = ... custom-call(
  ...)`` for a Mosaic kernel): ``op_name`` keeps what is before `` = `` and
  ``opcode`` the word before the first operand list. A ``while`` (the layer
  scan, the block's step scan) is itself an event that spans its children,
  which are on the same line; ``Async XLA Ops`` repeats the asynchronous ones
  with their whole flight time and is not read: an asynchronous collective
  therefore counts only while the core sits in its ``-start``/``-done`` ops,
  a synchronous one in full. The line ``XLA Modules`` holds
  one event per program execution, named ``jit_<python function>(<fingerprint>
  )``;
* ``/host:CPU`` holds host threads; ``jax.profiler.TraceAnnotation`` spans of
  the benchmark (``bm:*``) are on the line of the thread that made them
  (``python3``), on the same clock as the device planes.

The traced window is the ``bm:traced_window`` annotation: device events are
clipped to it, and ``busy`` is the union of the op intervals inside it.
"""

from __future__ import annotations

import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW = "bm:traced_window"
ANNOTATION_PREFIX = "bm:"

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
MOSAIC = re.compile(r"custom-call|tpu_custom_call|mosaic", re.I)
# ops that only wrap others on the ops line (their children are there too)
CONTAINER = {"while", "conditional", "call"}
OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")[:80]


def opcode(text: str) -> str:
    """The HLO opcode of an instruction text (``fusion``, ``while``,
    ``custom-call``, ``all-reduce-start``); the name's stem where the text is
    only a name."""
    head, sep, rest = text.partition(" = ")
    m = OPCODE.search(" " + rest) if sep else None
    return m.group(1) if m else re.sub(r"[.\d]+$", "", op_name(text))


def category(text: str) -> str:
    """collective | mosaic | other, from the op's opcode and name (jax may
    name an all-reduce ``psum.7``: the opcode tells). Matrix multiplications
    cannot be told this way: a large step's are plain ``fusion.N``."""
    both = f"{op_name(text)} {opcode(text)}"
    if COLLECTIVE.search(both):
        return "collective"
    if MOSAIC.search(both):
        return "mosaic"
    return "other"


def module_name(event_name: str) -> str:
    """``jit_fused_fn(8123...)`` -> ``jit_fused_fn``."""
    return event_name.split("(")[0].strip()


def union_seconds(intervals: List[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """(total, merged intervals) of the union of [start, end) intervals."""
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return sum(b - a for a, b in merged), merged


def _clip(a: float, b: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def reduce_profile(profile) -> Optional[dict]:
    """The reduction. ``profile`` is a ``ProfileData``. None if the trace has
    no device plane (a host-only trace has nothing to say about the chip)."""
    ns = 1e-9
    annotations: List[Tuple[str, float, float]] = []
    devices = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        annotations.append((ev.name, ev.start_ns * ns,
                                            (ev.start_ns + ev.duration_ns) * ns))
    if not devices:
        return None
    windows = [(a, b) for n, a, b in annotations if n == WINDOW]
    lo, hi = windows[0] if windows else (-float("inf"), float("inf"))

    per_device = []
    op_time: Dict[str, float] = defaultdict(float)
    cat_time: Dict[str, float] = defaultdict(float)
    mod_time: Dict[str, float] = defaultdict(float)
    mod_calls: Dict[str, int] = defaultdict(int)
    first_merged = None
    for idx in sorted(devices):
        intervals = []
        for line in devices[idx].lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    c = _clip(ev.start_ns * ns, (ev.start_ns + ev.duration_ns) * ns, lo, hi)
                    if c is None:
                        continue
                    intervals.append(c)
                    if opcode(ev.name) in CONTAINER:
                        continue
                    dur = c[1] - c[0]
                    op_time[op_name(ev.name)] += dur
                    cat_time[category(ev.name)] += dur
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    c = _clip(ev.start_ns * ns, (ev.start_ns + ev.duration_ns) * ns, lo, hi)
                    if c is not None:
                        mod_time[module_name(ev.name)] += c[1] - c[0]
                        mod_calls[module_name(ev.name)] += 1
        busy, merged = union_seconds(intervals)
        per_device.append(busy)
        if first_merged is None:
            first_merged = merged
    if not windows:                       # no annotation: the span of the events
        lo, hi = (first_merged[0][0], first_merged[-1][1]) if first_merged else (0.0, 0.0)
    n = len(per_device)

    # idle gaps of the first device, by what the host was doing in them
    gaps = []
    edge = lo
    for a, b in first_merged or []:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    if hi > edge:
        gaps.append((edge, hi))
    leaves = sorted((a, b, nm) for nm, a, b in annotations if nm != WINDOW)
    idle_by: Dict[str, float] = defaultdict(float)
    for ga, gb in gaps:
        covered = 0.0
        for a, b, nm in leaves:
            if a >= gb:
                break
            c = _clip(a, b, ga, gb)
            if c:
                idle_by[nm] += c[1] - c[0]
                covered += c[1] - c[0]
        if gb - ga > covered:
            idle_by["unannotated"] += gb - ga - covered

    def top(d: Dict[str, float], k: int = 10):
        return [[nm, s / n] for nm, s in sorted(d.items(), key=lambda kv: -kv[1])[:k]]

    return {
        "devices": n,
        "window_s": hi - lo,
        "busy_s": sum(per_device) / n,
        "busy_s_per_device": per_device,
        # seconds per device (means over the devices)
        "category_s": {k: v / n for k, v in cat_time.items()},
        "module_s": {k: v / n for k, v in mod_time.items()},
        "module_calls": {k: v / n for k, v in mod_calls.items()},
        "device_ops": top(op_time),
        "idle_gaps": [[nm, s] for nm, s in sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]],
        "longest_gap_s": max((b - a for a, b in gaps), default=0.0),
    }


def reduce_file(path: str) -> Optional[dict]:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def dump(path: str, per_line: int = 4) -> None:
    """Planes, lines, event counts and a few events with their stats."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:per_line]:
                stats = {k: (v if not isinstance(v, str) else v[:60]) for k, v in ev.stats}
                print(f"    {ev.name[:90]!r} start_ns={ev.start_ns:.0f} "
                      f"dur_ns={ev.duration_ns:.0f} {stats}")


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[2] == "--dump":
        dump(sys.argv[1])
    else:
        import json

        print(json.dumps(reduce_file(sys.argv[1]), indent=1))
