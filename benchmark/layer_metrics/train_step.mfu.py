"""Model FLOP/s utilization, in percent: forward + backward FLOPs a token
needs (``opcount.train_flops_per_token``; recomputation not counted) times
the tokens of a step over the median step time, over chips times the bf16
peak."""

import numpy as np

from benchmark import opcount


def read(record):
    steps = record.get("step_ms")
    if not steps or not record.get("tokens_per_step"):
        return None
    need = opcount.train_flops_per_token(record["config"], record["mix"]["seq_len"])
    rate = record["tokens_per_step"] / (float(np.median(steps)) / 1e3)
    return 100.0 * need * rate / (record["chips"] * record["peaks"]["bf16_flops_per_s"])
