"""Share of the bf16 peak an insert reaches, in percent: the FLOPs the traced
stretch's prompts NEED over their real tokens (``opcount_hybrid.insert_flops``:
every layer's weights, the recurrence one token at a time, causal attention in
the attention layers, one row of logits a prompt) over the peak, over the
device time of the insert programs in that stretch (``prefill.ms_per_call``'s
time). None without Mamba layers or a traced insert."""

from benchmark import opcount_hybrid

MODULE = "jit_insert_fn"


def read(record):
    cfg = opcount_hybrid.hybrid_config(record)
    if cfg is None:
        return None
    trace = record.get("device_trace") or {}
    busy = trace.get("module_s", {}).get(MODULE)
    lo, hi = record.get("traced") or (None, None)
    if not busy or lo is None:
        return None
    lens = [r["prompt_tokens"] for r in record["rows"]
            if r["stamps"] and lo <= r["stamps"][0] <= hi]
    if not lens:
        return None
    return 100.0 * opcount_hybrid.insert_flops(cfg, lens) / record["peaks"]["bf16_flops_per_s"] / busy
