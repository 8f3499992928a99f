"""Share of the compute roofline the prefill reaches, in percent: the FLOPs
the traced stretch's prompts NEED (their own lengths, top-k experts, one
logits row each; ``opcount.prefill_flops``) over the chip's bf16 peak, over
the device time of the insert-prefill programs in that stretch."""

from benchmark import opcount

MODULE = "jit_insert_fn"


def read(record):
    trace = record.get("device_trace") or {}
    busy = trace.get("module_s", {}).get(MODULE)
    lo, hi = record.get("traced") or (None, None)
    if not busy or lo is None:
        return None
    lens = [r["prompt_tokens"] for r in record["rows"]
            if r["stamps"] and lo <= r["stamps"][0] <= hi]
    if not lens:
        return None
    need = opcount.prefill_flops(record["config"], lens)
    return 100.0 * need / record["peaks"]["bf16_flops_per_s"] / busy
