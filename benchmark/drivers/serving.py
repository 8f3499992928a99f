"""Serving cells: the benchmark's own wall-clock loop around ``ServeEngine``.

The program is reached through its public entry points only:
``initialize_parallel_model`` (weights born on the device from the seed, in
one jitted call), ``CausalLM(...)`` and ``ServeEngine.submit`` /
``step_block`` / ``completed`` / ``rejected``; every ``CausalLM`` and
``ServeEngine`` option the configuration file does not name stays at the
program's default (gather decode, fused blocks of 8 steps, synchronous loop).

Open loop: the generator fixes every request's due time before the window
opens; the loop submits what is due, calls ``step_block()``, and sleeps to the
next due time only when ``step_block()`` had nothing to do. A request's clock
starts when it was DUE. Closed loop: each of ``clients`` callers sends its
next request when the last returned.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import time
from typing import List

import numpy as np

from benchmark import metrics, traffic
from benchmark.run import BenchmarkFailure, Context

# prefill buckets as examples/inference/runner.py::build_model picks them
BUCKET_LADDER = (128, 512, 2048, 4096)
PROBE_ROWS, PROBE_STEPS = 4, 4


def load(spec: str):
    module, _, attr = spec.partition(":")
    return getattr(importlib.import_module(module), attr)


def model_config(cfg: dict, rehearse: bool, **over):
    """The program's config object from the configuration file's sizes. A
    rehearsal runs float32 without the Pallas interpreter, to stay short."""
    import jax.numpy as jnp

    builder = cfg["builder"]
    dtype = jnp.float32 if rehearse else getattr(jnp, builder["dtype"])
    if rehearse:
        over = dict(over, use_flash_attention=False)
    fields = {ours: cfg[published] for ours, published in builder["fields"].items()}
    return load(builder["config"])(**fields, dtype=dtype, param_dtype=dtype, **over)


def init_rngs(seed: int) -> dict:
    """The seed as the program's initialisers take it. The key is of the
    ``rbg`` kind (the chip's own bit generator): the same initialisers, and
    billions of weights drawn in seconds where threefry takes tens."""
    import jax

    return {"params": jax.random.key(seed, impl="rbg")}


def build_lm(ctx: Context):
    """Weights from the seed, then ``CausalLM``."""
    import jax.numpy as jnp

    from neuronx_distributed_tpu.inference import CausalLM
    from neuronx_distributed_tpu.parallel import mesh
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_model,
        neuronx_distributed_config,
    )

    serving = ctx.cfg["serving"]
    max_seq_len = int(ctx.mix["max_seq_len"])
    mcfg = model_config(ctx.cfg, ctx.rehearse, max_seq_len=max_seq_len, remat_policy=None)
    model_cls = load(ctx.cfg["builder"]["model"])
    mesh.initialize_model_parallel(tensor_model_parallel_size=1, devices=ctx.devices)
    nxd = neuronx_distributed_config(tensor_parallel_size=1)
    model = initialize_parallel_model(nxd, lambda: model_cls(mcfg),
                                      jnp.zeros((1, 8), jnp.int32), rngs=init_rngs(ctx.seed))
    buckets = tuple(b for b in BUCKET_LADDER if b < max_seq_len)
    return CausalLM(mcfg, model.params, model_cls, buckets=buckets,
                    max_batch=serving["max_batch"], page_size=serving["page_size"],
                    prefix_cache=serving["prefix_cache"])


def buckets_used(lm, mix: dict) -> List[int]:
    lo, hi = traffic.length_range(mix["prompt_tokens"])
    return sorted({lm._bucket_for(n) for n in (lo, hi)}
                  | {b for b in lm.buckets if lo <= b <= hi})


def answers_decode(mix: dict) -> bool:
    return traffic.length_range(mix["answer_tokens"])[1] > 1


# ------------------------------------------------------------- correctness

def check_against_reference(ctx: Context, lm) -> dict:
    """Prefill logits of a seeded group of prompts, then teacher-forced decode
    steps through the paged cache, against the plain reference's full forward
    pass over the same tokens. Logits, relative to the reference's largest."""
    import jax
    import jax.numpy as jnp

    ref_cfg = ctx.cfg["reference"]
    reference = importlib.import_module(f"benchmark.reference.{ref_cfg['module']}")
    steps = PROBE_STEPS if answers_decode(ctx.mix) else 0
    rows = min(PROBE_ROWS, lm.max_batch)
    rng = np.random.RandomState(ctx.seed + 7919)
    lens = traffic.stratified_lengths(ctx.mix["prompt_tokens"], rows, rng)
    width = int(lens.max())
    prompts = np.zeros((rows, width), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = rng.randint(1, lm.config.vocab_size, (int(n),))
    forced = rng.randint(1, lm.config.vocab_size, (steps, rows)).astype(np.int32)

    session = lm.start_session()
    got = [np.asarray(lm.insert(session, np.arange(rows), prompts, lengths=lens,
                                reserve_tokens=steps + 1), np.float32)]
    for t in range(steps):
        full = np.zeros((lm.max_batch,), np.int32)
        full[:rows] = forced[t]
        got.append(np.asarray(lm.step(session, full), np.float32)[:rows])
    del session
    gc.collect()

    # the reference reads [prompt, forced tokens] at one padded width; causal
    # attention makes what follows a position irrelevant to it
    ids = np.zeros((rows, width + steps), np.int32)
    for i, n in enumerate(lens):
        ids[i, :n] = prompts[i, :n]
        ids[i, n: n + steps] = forced[:, i]
    pick = np.asarray(lens)[:, None] - 1 + np.arange(steps + 1)[None, :]     # (rows, steps+1)
    want = np.asarray(reference.forward(lm.params, jnp.asarray(ids), ctx.cfg, positions=pick),
                      np.float32).transpose(1, 0, 2)
    jax.clear_caches()
    gc.collect()

    got = np.stack(got)                                                   # (steps+1, rows, vocab)
    scale = float(np.abs(want).max())
    rel = np.abs(got - want).max(axis=-1) / scale                         # per position
    obs = {"positions": int(rel.size), "prompt_lens": lens.tolist(), "decode_steps": steps,
           "relative_median": float(np.median(rel)), "relative_max": float(rel.max()),
           "tolerance_median": ref_cfg["tolerance"], "tolerance_any": ref_cfg["tolerance_any"],
           "finite": bool(np.isfinite(got).all()),
           "argmax_agree": float((got.argmax(-1) == want.argmax(-1)).mean())}
    obs["ok"] = bool(obs["finite"] and obs["relative_median"] <= ref_cfg["tolerance"]
                     and obs["relative_max"] <= ref_cfg["tolerance_any"])
    return obs


# ------------------------------------------------------------------ warm-up

def warm_up(ctx: Context, lm, engine_kw: dict) -> None:
    """Drive every (rows, bucket) admission group the traffic can form, and
    the decode block, through ``submit``/``step_block``: the insert programs
    compile lazily per group shape, and so do the engine's small eager ops."""
    from neuronx_distributed_tpu.inference import ServeEngine

    engine = ServeEngine(lm, **engine_kw)
    rng = np.random.RandomState(ctx.seed + 104729)
    decode = answers_decode(ctx.mix)
    lo, hi = traffic.length_range(ctx.mix["prompt_tokens"])
    below = 0
    for bucket in buckets_used(lm, ctx.mix):
        n = int(np.clip(bucket, max(lo, below + 1), hi))   # a length this bucket takes
        below = bucket
        for rows in range(1, lm.max_batch + 1):
            budget = 2 * engine.block_steps + 2 if decode and rows == lm.max_batch else 1
            for _ in range(rows):
                engine.submit(rng.randint(1, lm.config.vocab_size, (n,)).astype(np.int32),
                              max_new_tokens=budget, arrival_block=engine.blocks)
            while engine.step_block():
                pass
    if decode:      # an insert into a running batch, as the window will see
        for budget in (3 * engine.block_steps, 1, 3):
            engine.submit(rng.randint(1, lm.config.vocab_size, (lo,)).astype(np.int32),
                          max_new_tokens=budget, arrival_block=engine.blocks)
            engine.step_block()
        while engine.step_block():
            pass
    if engine.rejected or any(len(c.tokens) == 0 for c in engine.completed):
        raise BenchmarkFailure("warm-up requests were rejected or came back empty")
    del engine
    gc.collect()


# --------------------------------------------------------------- the window

def start_profiler(trace_dir) -> None:
    """The device and the host's TraceMe spans, WITHOUT the Python call tracer
    (it logged 60 000 events a second here and slows the host it observes)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


def annotate(name: str, traced: bool):
    if not traced:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation("bm:" + name)


class Window:
    """One measured window over one fresh engine."""

    def __init__(self, ctx: Context, engine, seconds: float, traced: bool):
        self.ctx, self.engine, self.seconds, self.traced = ctx, engine, seconds, traced
        self.rows: List[dict] = []           # one per submitted request, submission order
        self.by_id = {}
        self.seen = 0                        # completions already looked at
        self.block_spans: List[tuple] = []   # (start, end, worked) of step_block calls
        self.t0 = 0.0
        self.trace_state = "off"
        self.trace_from = max(0.0, seconds - float(ctx.mix.get("trace_s", 6)))
        self.traced_from = self.traced_to = None     # the profiler's stretch, window clock
        self._window_annotation = None
        self.trace_written = (0.0, 0.0)      # (when the profiler was stopped, how long its write-out took)

    def clock(self, t: float) -> float:
        """``time.perf_counter()`` value ``t`` on the window's clock, which
        stands still while the profiler writes its trace out (30 s and more at
        a chat cell's rate): the loop can serve nobody then, and the stall is
        the harness's own, not the program's."""
        stopped, took = self.trace_written
        return t - self.t0 - (took if took and t > stopped else 0.0)

    def now(self) -> float:
        return self.clock(time.perf_counter())

    def submit(self, req: traffic.Req, due: float) -> None:
        with annotate("submit", self.traced):
            rid = self.engine.submit(req.prompt, max_new_tokens=req.max_new_tokens,
                                     arrival_block=self.engine.blocks)
        row = {"due": due, "submitted": self.now(), "stamps": [], "want": req.max_new_tokens,
               "prompt_tokens": int(req.prompt.size), "failed": False, "why": None}
        self.rows.append(row)
        if isinstance(rid, int):
            self.by_id[rid] = row
        else:
            row.update(failed=True, why=f"rejected:{getattr(rid, 'reason', '?')}")

    def harvest(self) -> List[dict]:
        """Rows of the requests completed since the last call."""
        done = []
        completed = self.engine.completed
        while self.seen < len(completed):
            c = completed[self.seen]
            self.seen += 1
            row = self.by_id.pop(c.request_id, None)
            if row is None:
                continue
            row["stamps"] = [self.clock(float(t)) for t in (c.token_ts if c.token_ts is not None else [])]
            if c.expired or c.cancelled or len(c.tokens) < row["want"]:
                row.update(failed=True, why=f"short:{c.finish_reason}:{len(c.tokens)}/{row['want']}")
            done.append(row)
        return done

    def step(self) -> bool:
        self._trace_edge()
        a = self.now()
        with annotate("step_block", self.traced):
            worked = self.engine.step_block()
        self.block_spans.append((a, self.now(), bool(worked)))
        return worked

    def sleep_until(self, t: float) -> None:
        with annotate("sleep_to_next_arrival", self.traced):
            time.sleep(max(0.0, t - self.now()))

    def _trace_edge(self) -> None:
        """The profiler runs over the window's last ``trace_s`` seconds, so
        that writing the trace out stalls nothing that is measured."""
        if not self.traced or self.ctx.rehearse:
            return
        import jax

        if self.trace_state == "off" and self.now() >= self.trace_from:
            start_profiler(self.ctx.trace_dir)
            self._window_annotation = jax.profiler.TraceAnnotation("bm:traced_window")
            self._window_annotation.__enter__()
            self.trace_state = "on"
            self.traced_from = self.now()

    def stop_trace(self) -> None:
        if self.trace_state == "on":
            import jax

            self._window_annotation.__exit__(None, None, None)
            self.traced_to = self.now()
            stopped = time.perf_counter()
            jax.profiler.stop_trace()
            self.trace_written = (stopped, time.perf_counter() - stopped)
            self.trace_state = "done"

    def drain(self) -> None:
        """After the window: finish what was submitted, within the mix's
        stated allowance; what is still unfinished then has failed."""
        limit = self.seconds + float(self.ctx.mix["drain_s"])
        while self.by_id and self.now() < limit:
            if not self.step():
                break
            self.harvest()
        for row in self.by_id.values():
            row.update(failed=True, why="unfinished_after_drain")


def run_open(w: Window, reqs: List[traffic.Req]) -> None:
    i, n = 0, len(reqs)
    w.t0 = time.perf_counter()
    while w.now() < w.seconds:
        while i < n and reqs[i].due_s <= w.now():
            w.submit(reqs[i], reqs[i].due_s)
            i += 1
        if not w.step():
            w.sleep_until(min(reqs[i].due_s if i < n else w.seconds, w.seconds))
        w.harvest()
    w.stop_trace()
    for r in reqs[i:]:                     # due inside the window, a block was running
        w.submit(r, r.due_s)
    w.drain()


def run_closed(w: Window, stream, clients: int) -> None:
    w.t0 = time.perf_counter()
    free = [0.0] * clients                 # when each idle client became free
    while w.now() < w.seconds:
        for due in free:
            w.submit(next(stream), due)
        free = []
        w.step()
        for row in w.harvest():            # the caller sends again when the reply is back
            free.append(row["stamps"][-1] if row["stamps"] else w.now())
    w.stop_trace()
    w.drain()


# --------------------------------------------------------------------- run

def measure(ctx: Context, lm, engine_kw: dict, seconds: float, traced: bool,
            rate_per_s=None) -> Window:
    """One window over a fresh engine (``sweep.py`` calls this per rate)."""
    from neuronx_distributed_tpu.inference import ServeEngine

    mix = ctx.mix
    engine = ServeEngine(lm, **engine_kw, trace=traced)
    w = Window(ctx, engine, seconds, traced)
    if mix["loop"] == "open":
        run_open(w, traffic.open_loop(mix, lm.config.vocab_size, ctx.seed, seconds, rate_per_s))
    elif mix["loop"] == "closed":
        run_closed(w, traffic.closed_loop(mix, lm.config.vocab_size, ctx.seed), int(mix["clients"]))
    else:
        raise BenchmarkFailure(f"unknown loop {mix['loop']!r}")
    return w


def run(ctx: Context) -> dict:
    import jax

    t0 = time.perf_counter()
    lm = build_lm(ctx)
    jax.block_until_ready(lm.params)
    build_s = time.perf_counter() - t0
    ctx.emit("built", build_s=round(build_s, 2))
    lm.compile()
    ctx.emit("compiled_decode")
    engine_kw = dict(rng=jax.random.key(ctx.seed))
    reference = check_against_reference(ctx, lm)
    ctx.emit("reference", **reference)
    t0 = time.perf_counter()
    warm_up(ctx, lm, engine_kw)
    warm_s = time.perf_counter() - t0
    at_open = ctx.watch.counts()
    programs = dict(lm.compile_ms)
    ctx.emit("setup", build_s=round(build_s, 2), warm_up_s=round(warm_s, 2),
             programs=len(programs), buckets=list(lm.buckets),
             buckets_used=buckets_used(lm, ctx.mix), **at_open)

    setup_s = ctx.since_start()
    w = measure(ctx, lm, engine_kw, ctx.seconds, ctx.traced)
    at_close = ctx.watch.counts()
    if at_close["compiles"] != at_open["compiles"]:
        new = sorted(set(lm.compile_ms) - set(programs))
        raise BenchmarkFailure(
            f"{at_close['compiles'] - at_open['compiles']} compilation(s) inside the window: "
            f"programs {new or 'none of CausalLM (an eager op)'}; "
            f"{ctx.watch.seen[at_open['compiles']:]}")

    rows, engine = w.rows, w.engine
    late = np.asarray([r["submitted"] - r["due"] for r in rows], np.float64) * 1e3
    lateness = {"p50": float(np.median(late)) if late.size else None,
                "max": float(late.max()) if late.size else None}
    failed = [r for r in rows if r["failed"]]
    e2e = metrics.serving_end_to_end(rows, ctx.seconds)
    pool = engine.session.paged
    fused = lm.compile_session_decode_fused(engine.block_steps, engine.slot_sampler,
                                            engine.pad_token_id) if answers_decode(ctx.mix) else None
    mem = fused.memory_analysis() if fused is not None else None
    ctx.emit("window", attempted=len(rows), failed=len(failed),
             why_failed=sorted({r["why"] for r in failed}),
             generator_late_ms_p50=lateness["p50"], generator_late_ms_max=lateness["max"],
             compiles_in_window=0, blocks=int(engine.stats["blocks"]),
             decode_blocks=int(engine.stats["decode_blocks"]), inserts=int(engine.stats["inserts"]),
             end_to_end=None if ctx.rehearse else e2e)
    record = {
        "correct": reference["ok"], "reference": reference,
        "compared": {"logit_gap_median": [reference["relative_median"], reference["tolerance_median"]],
                     "logit_gap_max": [reference["relative_max"], reference["tolerance_any"]]},
        "attempted": len(rows), "failed": len(failed),
        "setup_s": setup_s, "end_to_end": e2e, "rows": rows,
        "block_spans": w.block_spans,
        "traced": [w.traced_from, w.traced_to], "trace_write_s": w.trace_written[1],
        "host_spans": [dict(e, ts=w.clock(e["ts"])) for e in engine.tracer.events()
                       if e["ph"] == "X"] if ctx.traced else [],
        "engine_stats": {k: int(v) for k, v in engine.stats.items()},
        "engine": {"block_steps": engine.block_steps, "max_batch": lm.max_batch,
                   "max_seq_len": lm.config.max_seq_len},
        "pool": {"pages": int(lm.config.page_pool_pages),
                 "pages_in_use_peak": int(pool.stats["pages_in_use_peak"]),
                 "prefix_hits": int(pool.stats["prefix_hits"]),
                 "bytes": int(lm.kv_cache_bytes()["kv_bytes"])},
        "compile": {"compile_ms": programs, "programs": len(programs),
                    "compile_s": sum(programs.values()) / 1e3, "build_s": build_s,
                    "warm_up_s": warm_s},
        "fused_decode_memory": None if mem is None else {
            "temp_bytes": int(mem.temp_size_in_bytes),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes)},
        "generator_late_ms": lateness,
        "counts": {"attempted": len(rows), "failed": len(failed),
                   "blocks": int(engine.stats["blocks"]), "inserts": int(engine.stats["inserts"]),
                   "programs": len(programs), "compiles_in_window": 0},
    }
    return record
