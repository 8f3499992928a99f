"""Share of its memory roofline the decode step reaches, in percent: the
bytes a live step NEEDS (attention, router and head weights once, the experts
the step READ, the cached keys and values of its live rows;
``opcount.decode_step_bytes``) over the chip's HBM bandwidth, over the
measured device time of a live step (``decode.step_ms``). Decode at batch 8 is
bound by bytes, not FLOPs. Rows, context and experts read are means over the
LIVE steps of the traced decode blocks (``decode_steps.traced_decode``), not
over the stretch's wall time; where the program recorded no touched-expert
counter the experts are ``min(experts held, rows x k)``, an upper bound."""

from benchmark import decode_steps, opcount


def read(record):
    ran = decode_steps.traced_decode(record)
    if ran is None or not record.get("peaks"):
        return None
    need = opcount.decode_step_bytes(record["config"], ran["rows"], ran["context_tokens"],
                                     experts_read=ran["experts_per_layer_step"])
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / ran["step_s"]
