"""The decode steps that RAN in a traced stretch, from what the record holds.

The engine admits between fused blocks, so a row is live from a block's first
step; tokens of one block share the stamp of the fetch that delivered them
(``Completion.token_ts``), and a request's first stamp is its insert's. A row
with c tokens under one block's stamp was therefore live in that block's steps
1..c, and the block ran ``max(c)`` LIVE steps (a step in which at least one
row was live); the steps after its last row finished are dead: the program
still runs them, they read no expert and deliver nothing. Nothing here is the
program's: rows, ``traced``, ``engine`` and ``engine_stats``.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional

MODULE = "jit_fused_fn"


def blocks_by_stamp(rows: List[dict]) -> Dict[float, List[tuple]]:
    """stamp -> [(tokens, context at the block's first step), ...], one entry
    per row that was live in the block fetched at that stamp."""
    blocks: Dict[float, List[tuple]] = defaultdict(list)
    for row in rows:
        stamps = row.get("stamps") or []
        at = 1                                   # tokens this row held before the block
        while at < len(stamps):
            end = at
            while end < len(stamps) and stamps[end] == stamps[at]:
                end += 1
            blocks[stamps[at]].append((end - at, row["prompt_tokens"] + at))
            at = end
    return blocks


def traced_decode(record: dict) -> Optional[dict]:
    """What the decode blocks fetched inside the traced stretch did: None
    where the record lacks the stretch, the module's time or any such block,
    or where the rows show another number of blocks in the stretch than the
    module executed (a request that never finished leaves no stamps: the live
    steps cannot be counted then).

    ``rows`` and ``context_tokens`` are means over the live steps (context
    summed over a step's live rows). ``experts_per_layer_step`` is the experts
    a live layer-step READ: the whole window's ``moe_experts_touched`` over
    its live layer-steps, None without the counter (the caller falls back)."""
    trace = record.get("device_trace") or {}
    calls = trace.get("module_calls", {}).get(MODULE)
    lo, hi = record.get("traced") or (None, None)
    if not calls or lo is None or hi is None:
        return None
    every = blocks_by_stamp(record.get("rows") or [])
    inside = [rows for stamp, rows in every.items() if lo < stamp <= hi]
    if not inside or len(inside) != calls:
        return None
    live = sum(max(c for c, _ in rows) for rows in inside)
    row_steps = sum(c for rows in inside for c, _ in rows)
    # a row live for c steps from context x reads x, x+1, ... x+c-1 tokens
    context = sum(c * x + c * (c - 1) / 2 for rows in inside for c, x in rows)
    out = {"blocks": len(inside), "live_steps": live,
           "rows": row_steps / live, "context_tokens": context / live,
           "module_s": trace["module_s"][MODULE],
           "step_s": trace["module_s"][MODULE] / live,      # device time of a live step
           "experts_per_layer_step": None}
    layers = (record.get("config") or {}).get("num_hidden_layers")
    stats = record.get("engine_stats") or {}
    if (layers and (record.get("config") or {}).get("num_local_experts", 1) > 1
            and stats.get("moe_experts_touched") is not None
            and len(every) == stats.get("decode_blocks")):
        window_live = sum(max(c for c, _ in rows) for rows in every.values())
        out["experts_per_layer_step"] = stats["moe_experts_touched"] / (window_live * layers)
    return out
