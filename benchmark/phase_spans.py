"""The engine's own spans of a round, read from a record's ``host_spans``.

Since PR 39 a traced ``ServeEngine.step_block()`` is one ``step_block`` span
on the tracer's ``(lane, "phases")`` track, tiled by ``admit``, ``observe``,
``launch``, the dispatch lane's ``fetch`` and ``harvest``; an insert is an
``admission`` span inside ``admit`` (args ``rows``, ``bucket``, ``decoding`` =
the rows it stalls, ``rids``), and the cache's host half of an insert is
``cache_plan`` and ``cache_commit`` on ``("cache", "pool")``. The driver copies
every complete span into ``host_spans`` on the window's clock, in seconds.

A reader under ``layer_metrics/`` is two lines over one of the functions here.
EVERY function answers ``None`` on a record with no span on a ``phases`` track:
that is a program from before PR 39 (whose ``queued`` span also ended somewhere
else, at the first token), an untraced run or an empty record. None raises.

The window: rounds, admissions, blocks and requests whose span STARTS inside
``[0, seconds)`` of the window's clock (the drain after it serves no arrivals;
a record without ``seconds`` is taken whole); what lies inside one of them
goes with it, also past the window's end. Self time: a span less the ``fetch``
and ``insert_fetch`` spans that start inside it, which are the host blocked on
the device and no work of its own. A round WORKED where ``step_block()`` said
so (``args["worked"]``); a phase belongs to the round whose span holds its
start. Means, not medians, so that the four phases of ``per_worked_round`` add
up: their sum is the worked rounds' mean ``step_block`` span less the ``fetch``
and ``insert_fetch`` spans inside it.
"""

from bisect import bisect_left, bisect_right

BLOCKED = ("fetch", "insert_fetch")


def _start(e):
    return e["ts"]


def phases(record):
    """The record's spans by name: the ``phases`` track's, the dispatch lane's
    ``fetch`` / ``insert_fetch``, the block lane's ``decode_block``, the two
    cache spans and the requests' ``queued``; each list sorted by start. None
    where the record holds no span on a ``phases`` track."""
    events = record.get("host_spans") or []
    if not any(e["lane"][1] == "phases" for e in events):
        return None
    wanted = {"phases": None, "dispatch": BLOCKED, "blocks": ("decode_block",),
              "pool": ("cache_plan", "cache_commit")}
    by_name = {}
    for e in events:
        group, track = e["lane"]
        names = ("queued",) if group == "req" else wanted.get(track, ())
        if names is None or e["name"] in names:
            by_name.setdefault(e["name"], []).append(e)
    for spans in by_name.values():
        spans.sort(key=_start)
    return by_name


def _in_window(record, spans):
    limit = record.get("seconds")
    return [e for e in spans if e["ts"] >= 0 and (limit is None or e["ts"] < limit)]


def _blocked_inside(by_name, span):
    """Seconds of ``fetch`` / ``insert_fetch`` spans that start inside ``span``."""
    a, b = span["ts"], span["ts"] + span["dur"]
    total = 0.0
    for name in BLOCKED:
        spans = by_name.get(name, [])
        inside = spans[bisect_left(spans, a, key=_start):bisect_left(spans, b, key=_start)]
        total += sum(e["dur"] for e in inside)
    return total


def _worked_rounds(record, by_name):
    return [e for e in _in_window(record, by_name.get("step_block", []))
            if (e.get("args") or {}).get("worked")]


def _of_rounds(by_name, name, rounds):
    """Spans called ``name`` whose start lies inside one of ``rounds`` (which
    do not overlap, sorted by start)."""
    out = []
    for e in by_name.get(name, []):
        i = bisect_right(rounds, e["ts"], key=_start) - 1
        if i >= 0 and e["ts"] < rounds[i]["ts"] + rounds[i]["dur"]:
            out.append(e)
    return out


def per_worked_round(record, name):
    """Milliseconds of self time of the worked rounds' ``name`` spans, a
    worked round."""
    by_name = phases(record)
    if by_name is None:
        return None
    rounds = _worked_rounds(record, by_name)
    if not rounds:
        return None
    spans = _of_rounds(by_name, name, rounds)
    return 1e3 * sum(e["dur"] - _blocked_inside(by_name, e) for e in spans) / len(rounds)


def round_host_ms(record):
    """Milliseconds a worked round's ``step_block`` span lasted less the
    ``fetch`` and ``insert_fetch`` spans inside it, mean: the host's whole
    time a round, which the four phases add up to."""
    by_name = phases(record)
    if by_name is None:
        return None
    rounds = _worked_rounds(record, by_name)
    if not rounds:
        return None
    return 1e3 * sum(e["dur"] - _blocked_inside(by_name, e) for e in rounds) / len(rounds)


def insert_stall_per_block(record):
    """Milliseconds of ``admission`` spans that began with rows decoding
    (``args["decoding"]`` > 0), their blocked wait included, a ``decode_block``
    span: what the inserts add to the wall of a block of ``block_steps``
    tokens, for the rows that stood still meanwhile."""
    by_name = phases(record)
    if by_name is None:
        return None
    blocks = _in_window(record, by_name.get("decode_block", []))
    if not blocks:
        return None
    stalls = [e["dur"] for e in _in_window(record, by_name.get("admission", []))
              if (e.get("args") or {}).get("decoding", 0) > 0]
    return 1e3 * sum(stalls) / len(blocks)


def queue_wait_mean(record):
    """Milliseconds from submit to the slot claimed, mean over the requests
    submitted in the window that got a slot: their ``queued`` spans."""
    by_name = phases(record)
    if by_name is None:
        return None
    waits = _in_window(record, by_name.get("queued", []))
    return 1e3 * sum(e["dur"] for e in waits) / len(waits) if waits else None


def cache_host_per_insert(record):
    """Milliseconds of ``cache_plan`` and ``cache_commit`` spans an
    ``admission`` span, over the window's admissions and the cache spans
    inside them: the cache layer's host work an insert."""
    by_name = phases(record)
    if by_name is None:
        return None
    admissions = _in_window(record, by_name.get("admission", []))
    if not admissions:
        return None
    work = sum(e["dur"] for name in ("cache_plan", "cache_commit")
               for e in _of_rounds(by_name, name, admissions))
    return 1e3 * work / len(admissions)
