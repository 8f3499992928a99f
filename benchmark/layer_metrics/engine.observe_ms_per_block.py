"""Host milliseconds a worked round spent in the engine's ``observe`` span:
``_observe_block()``'s gauges and counter tracks, the SLO monitor and the
incident detectors, which run every round whether tracing is on or not; the
window's sum over its worked ``step_block`` rounds. None on a program without
the spans."""

from benchmark import phase_spans


def read(record):
    return phase_spans.per_worked_round(record, "observe")
