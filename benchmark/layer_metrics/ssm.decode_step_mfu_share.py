"""Share of the chip's peak the WHOLE live decode step reaches, in percent:
the least time the step's needed bytes and operations can take
(``opcount_hybrid.decode_step_roofline_s``: every weight once, the live rows'
state read and written, their cached tokens in the attention layers; the
larger of bytes / HBM rate and FLOPs / bf16 peak) over the measured device
time of a live step. None without Mamba layers or a traced decode block."""

from benchmark import decode_steps, opcount_hybrid


def read(record):
    cfg = opcount_hybrid.hybrid_config(record)
    if cfg is None:
        return None
    ran = decode_steps.traced_decode(record)
    if ran is None:
        return None
    least = opcount_hybrid.decode_step_roofline_s(cfg, ran["rows"], ran["context_tokens"],
                                                  record["peaks"])
    return 100.0 * least / ran["step_s"]
