"""Milliseconds the inserts add to the wall of a decode block: the window's
``admission`` spans that began with rows decoding (``args["decoding"]`` > 0:
those rows stand still while the insert's program runs and its first tokens
are fetched) summed, over its ``decode_block`` spans. Divided by the block's
steps it is the part of ``tpot_ms_p50 - decode.step_ms`` that is other
requests' inserts. None on a program without the spans."""

from benchmark import phase_spans


def read(record):
    return phase_spans.insert_stall_per_block(record)
