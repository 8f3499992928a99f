"""Host milliseconds a worked round spent in the engine's ``harvest`` span:
from the return of the block's ``fetch`` to the round's return, so the
``_record`` loop over the fetched tokens, ``_expire_decoding`` and the last
``_retire_finished``; the window's sum over its worked ``step_block`` rounds.
None on a program without the spans."""

from benchmark import phase_spans


def read(record):
    return phase_spans.per_worked_round(record, "harvest")
