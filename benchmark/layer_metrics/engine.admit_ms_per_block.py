"""Host milliseconds a worked round spent admitting: the engine's ``admit``
spans (``queue.advance``, parks, replays, both ``_admit()`` passes with their
``admission`` spans, the retires between them, chunked prefill) less the
``insert_fetch`` spans inside them, where the host only waits for the insert's
first tokens; the window's sum over its worked ``step_block`` rounds. With
``observe``, ``launch`` and ``harvest`` it adds up to the host's whole time a
round (``phase_spans.round_host_ms``). None on a program without the spans."""

from benchmark import phase_spans


def read(record):
    return phase_spans.per_worked_round(record, "admit")
