"""Share of the bf16 peak an insert reaches, in percent: the FLOPs the traced
stretch's prompts NEED over their real tokens (``opcount_window.insert_flops``:
every weight outside the routed experts, the held picks only as
``moe_insert_assignments`` counts them, the triangle in the full layers and the
band in the window layers, one row of logits a prompt) over the peak, over the
device time of the insert programs in that stretch (``prefill.ms_per_call``'s
time). None without window layers, the routing counter or a traced insert."""

from benchmark import opcount_window

MODULE = "jit_insert_fn"


def read(record):
    cfg = opcount_window.window_config(record)
    stats = record.get("engine_stats") or {}
    trace = record.get("device_trace") or {}
    busy = trace.get("module_s", {}).get(MODULE)
    lo, hi = record.get("traced") or (None, None)
    if cfg is None or not busy or lo is None or stats.get("moe_insert_assignments") is None:
        return None
    inserted = [r["prompt_tokens"] for r in record["rows"] if r["stamps"]]
    lens = [r["prompt_tokens"] for r in record["rows"]
            if r["stamps"] and lo <= r["stamps"][0] <= hi]
    if not lens:
        return None
    # held picks a real token, summed over the expert layers, over the window
    picks = min(stats["moe_insert_assignments"] / sum(inserted),
                cfg["num_experts_per_tok"] * opcount_window.expert_layers(cfg))
    return (100.0 * opcount_window.insert_flops(cfg, lens, picks)
            / record["peaks"]["bf16_flops_per_s"] / busy)
