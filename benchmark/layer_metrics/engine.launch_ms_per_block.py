"""Host milliseconds a worked round spent in the engine's ``launch`` span:
from the end of ``observe`` to the return of the fused program's call, so the
uploads of the block's arguments and the enqueue (it holds the dispatch lane's
``decode`` span, which ``engine.host_ms_per_block`` subtracts); the window's
sum over its worked ``step_block`` rounds. None on a program without the
spans."""

from benchmark import phase_spans


def read(record):
    return phase_spans.per_worked_round(record, "launch")
