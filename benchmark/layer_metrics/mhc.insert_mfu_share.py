"""Share of the bf16 peak an insert of a model that carries several residual
streams reaches, in percent: the FLOPs the traced stretch's prompts NEED over
their real tokens (``opcount_mhc.insert_flops``: every weight outside the
experts, the stream mixes' projections among them, ``num_experts_per_tok``
experts a token, the triangle, one row of logits a prompt) over the peak, over
the device time of the insert programs in that stretch. The streams' own
traffic (four hidden states read and written a sub-block) is bandwidth the
count does not hold: it shows as a lower share. None without ``hc_mult`` in the
configuration, the program's ``mhc_mix_tokens`` counter or a traced insert."""

from benchmark import opcount_mhc

MODULE = "jit_insert_fn"


def read(record):
    cfg = opcount_mhc.mhc_config(record)
    stats = record.get("engine_stats") or {}
    busy = (record.get("device_trace") or {}).get("module_s", {}).get(MODULE)
    lo, hi = record.get("traced") or (None, None)
    if cfg is None or not busy or lo is None or not stats.get("mhc_mix_tokens"):
        return None
    lens = [r["prompt_tokens"] for r in record["rows"]
            if r["stamps"] and lo <= r["stamps"][0] <= hi]
    if not lens:
        return None
    return (100.0 * opcount_mhc.insert_flops(cfg, lens)
            / record["peaks"]["bf16_flops_per_s"] / busy)
