#!/usr/bin/env python3
"""Does the system still start on the chip?  The quickest proof there is.

One process drives the main path once, through the entry points a user
calls, at the published Llama-2-7B widths (hidden 4096, FFN 11008, 32 heads
x 128, vocab 32000, bf16) with only DEPTH cut to what one 16 GB TPU v5e
holds, on seeded random weights:

* serve: ``runner.build_model`` -> ``CausalLM`` (buckets 128/512, paged
  cache) -> ``ServeEngine(block_steps=16)`` answering a dozen greedy
  requests from ``synthetic_trace``/``run_trace``; the fused engine's
  streams against the stepwise oracle's, prefill logits against the plain
  float32 forward, the Pallas kernels looked up in the compiled programs,
  no compile after warm-up;
* train: the ``llama2_tp_zero1`` example's model/optimizer/step
  construction, three steps at batch 8 x 2048 on one repeated batch.

``python chip_smoke.py``            one chip (what the driver runs)
``python chip_smoke.py --chips 4``  only the four-chip path: TP=4 serving
                                    and TP=4 + SP + ZeRO-1 training, each
                                    against a TP=1 world on the same seeds
``python chip_smoke.py --rehearse`` the same control flow at tiny widths on
                                    host devices, kernels interpreted (add
                                    ``--chips 4`` under ``XLA_FLAGS=
                                    --xla_force_host_platform_device_count=4``)

Without ``--rehearse`` it refuses to start unless JAX's first device is a
TPU, and any failed check ends the run with a non-zero exit code. Every
phase prints one JSON line of observations; the last line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

PROMPT_LENS = (40, 100, 200, 400)   # both prefill buckets: 128 and 512
NUM_REQUESTS = 12
NEW_TOKENS = 64
BLOCK_STEPS = 16
MAX_BATCH = 8
PAGE_SIZE = 16
PROBE_ROWS, PROBE_LEN, PROBE_STEPS = 4, 100, 16

# Tolerances, each beside its reason. Logit bounds are RELATIVE to the
# reference's largest |logit| in the comparison (random-weight logits have
# no natural scale); each is about four times what the v5e showed in PR 21,
# so that it catches a broken path, not a different rounding.
#
# bf16 serving path vs the float32 plain forward: every bf16 rounding is
# <= 2^-9 relative and the residual stream is rounded a handful of times in
# each of 16 layers. Measured 0.005.
TOL_PREFILL_VS_F32 = 0.02
# TP=4 vs TP=1: the same math with bf16 partial sums reduced across four
# chips in another order. Measured 0.009 on the logits. The loss is a mean
# over 16k tokens, so the roundings average out: measured 1e-5 relative,
# and a wrong shard or a missing reduction moves it by far more than 1e-3.
TOL_TP_LOGITS = 0.03
TOL_TP_LOSS = 1e-3
# greedy streams of two paths that agree within a tolerance still part ways
# at near-tied argmaxes, and once parted never rejoin. Decision agreement
# counts each stream up to and including its first differing token; a
# broken kernel parts at the first token or two (agreement ~0.5), a sound
# one after tens of tokens.
MIN_DECISION_AGREEMENT = 0.90


@dataclasses.dataclass(frozen=True)
class Sizes:
    serve_layers: int
    train_layers: int
    max_seq_len: int
    page_pool_pages: int
    train_batch: int
    train_seq: int
    widths: dict    # overrides of the published widths; empty on the chip
    why: str


REAL = Sizes(
    serve_layers=16, train_layers=2, max_seq_len=1024, page_pool_pages=640,
    train_batch=8, train_seq=2048, widths={},
    why=("serve: 16 of 32 layers = 6.5 GiB of bf16 weights plus a 2.5 GiB "
         "page pool (640 pages x 16 tokens x 16 layers: the 512 that 8 slots "
         "x 1024 tokens need and 128 for cached prefixes). The pool cannot "
         "fill the rest of the 15.75 GiB: the compiled fused decode holds "
         "it 2.85 times over (argument plus 4.6 GiB of temporaries, 13.7 "
         "GiB in all by the compiler's memory analysis for a described "
         "v5e; at 1024 pages it is refused with 16.27 of 15.75 GiB). "
         "train: 2 layers = 0.67 B parameters whose bf16 copy, fp32 "
         "masters and two fp32 Adam moments are 8.7 GiB of step arguments "
         "before activations at batch 8 x 2048"))
REHEARSAL = Sizes(
    serve_layers=2, train_layers=2, max_seq_len=1024, page_pool_pages=600,
    train_batch=4, train_seq=256,
    widths=dict(hidden_size=64, intermediate_size=128, num_heads=4,
                num_kv_heads=4, vocab_size=512),
    why="rehearsal: tiny widths, host devices, Pallas kernels interpreted")


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **obs) -> None:
    print(json.dumps({"phase": phase, **obs}), flush=True)


class CompileWatch:
    """Counts XLA compile requests and persistent-cache hits/misses."""

    def __init__(self, jax):
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def counts(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.hits,
                "cache_misses": self.misses}


def memory(devices) -> list:
    """Per-device ``memory_stats()`` (None on host devices)."""
    out = []
    for d in devices:
        s = d.memory_stats()
        out.append(None if s is None else
                   {"bytes_in_use": s["bytes_in_use"],
                    "peak_bytes_in_use": s["peak_bytes_in_use"]})
    return out


def release() -> None:
    """Drop what the finished phase left on the device."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def require_mosaic(text: str, at_least: int, what: str, rehearse: bool) -> int:
    """The compiled program really holds the Pallas kernel(s). Interpreted
    kernels (rehearsal) lower to plain HLO and cannot be told apart."""
    n = text.count("tpu_custom_call")
    if not rehearse:
        check(n >= at_least,
              f"{what}: {n} tpu_custom_call in the compiled text, expected "
              f">= {at_least} — the Pallas kernel did not run compiled")
    return n


def collectives(text: str) -> dict:
    return {k: text.count(k + "(") + text.count(k + "-start(")
            for k in ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all")}


# ----------------------------------------------------------------- serving

def serving_stack(sizes: Sizes, tp: int):
    """(lm, cfg) through the runner's own builder."""
    import runner

    args = argparse.Namespace(
        cmd="serve", model="llama", preset="llama2_7b", tiny=False,
        num_layers=sizes.serve_layers, hf_checkpoint=None,
        max_seq_len=sizes.max_seq_len, max_batch=MAX_BATCH,
        tensor_parallel_size=tp, quantize=False, paged=True,
        page_size=PAGE_SIZE, page_pool_pages=sizes.page_pool_pages,
        no_prefix_cache=False, kv_dtype=None)
    # flash attention is asked for by name, never derived from the backend
    cfg = dataclasses.replace(runner.build_config(args),
                              use_flash_attention=True, **sizes.widths)
    return runner.build_model(args, cfg)


def make_trace(cfg, seed: int):
    from neuronx_distributed_tpu.inference.replay import synthetic_trace

    return synthetic_trace(NUM_REQUESTS, cfg.vocab_size, prompt_lens=PROMPT_LENS,
                           max_new_tokens=NEW_TOKENS, seed=seed)


def serve(lm, trace, seed: int, fused: bool = True):
    """One engine, one trace, run to the end: (streams, report)."""
    import jax

    from neuronx_distributed_tpu.inference import ServeEngine
    from neuronx_distributed_tpu.inference.replay import run_trace

    eng = ServeEngine(lm, block_steps=BLOCK_STEPS, fused=fused,
                      rng=jax.random.key(seed))
    report = run_trace(eng, trace, max_blocks=2000)
    streams = {c.request_id: c.tokens.tolist() for c in eng.completed}
    rejected = len(eng.rejected)
    # the engine's page pool goes with it, before the next one is made: two
    # pools beside the weights do not fit the chip
    del eng
    gc.collect()
    check(len(streams) == len(trace) and not rejected,
          f"{len(streams)} of {len(trace)} requests completed, "
          f"{rejected} rejected (fused={fused})")
    short = {r: len(t) for r, t in streams.items() if len(t) != NEW_TOKENS}
    check(not short, f"requests without their {NEW_TOKENS} tokens: {short}")
    return streams, report


def decision_agreement(ref: dict, got: dict) -> float:
    """Greedy decisions made on identical context that agree: each stream
    counts up to and including its first differing token."""
    agree = total = 0
    for rid, r in ref.items():
        g = got[rid]
        same = next((i for i, (a, b) in enumerate(zip(r, g)) if a != b),
                    len(r))
        agree += same
        total += same + (1 if same < len(r) else 0)
    return agree / total


def probe_prompts(cfg, seed: int):
    import numpy as np

    rs = np.random.RandomState(seed + 17)
    return rs.randint(1, cfg.vocab_size, (PROBE_ROWS, PROBE_LEN)).astype(np.int32)


def probe(lm, prompts, forced=None):
    """Insert the probe prompts and take PROBE_STEPS decode steps through
    the session API the engine itself drives. ``forced`` (steps, rows)
    teacher-forces another run's tokens so that every step's logits are
    comparable; without it the run follows its own argmax. Returns
    (logits (steps+1, rows, vocab) float32, tokens (steps, rows))."""
    import numpy as np

    session = lm.start_session()
    rows = np.arange(PROBE_ROWS)
    out = [np.asarray(lm.insert(session, rows, prompts,
                                reserve_tokens=PROBE_STEPS + 1), np.float32)]
    toks = []
    for t in range(PROBE_STEPS):
        tok = forced[t] if forced is not None else out[-1].argmax(-1)
        toks.append(np.asarray(tok))
        full = np.zeros((lm.max_batch,), np.int32)
        full[:PROBE_ROWS] = tok
        out.append(np.asarray(lm.step(session, full), np.float32)[:PROBE_ROWS])
    del session
    gc.collect()
    return np.stack(out), np.stack(toks)


def compare_logits(ref, got, tol: float, what: str) -> dict:
    """max |delta| relative to the reference's logit scale within ``tol``,
    and every decision whose reference margin clears twice that noise
    agrees (a nearer tie may legitimately flip)."""
    import numpy as np

    check(np.isfinite(got).all(), f"{what}: non-finite logits")
    scale = float(np.abs(ref).max())
    delta = float(np.abs(got - ref).max())
    top2 = np.sort(ref, axis=-1)[..., -2:]
    margin = top2[..., 1] - top2[..., 0]
    same = ref.argmax(-1) == got.argmax(-1)
    clear = margin > 2 * tol * scale
    obs = {"max_abs_delta": round(delta, 5), "logit_scale": round(scale, 4),
           "relative": round(delta / scale, 5), "tolerance": tol,
           "decisions": int(same.size), "decisions_agree": int(same.sum()),
           "clear_decisions": int(clear.sum())}
    check(delta <= tol * scale, f"{what}: logits differ by {obs}")
    check(bool(same[clear].all()),
          f"{what}: a decision with a clear margin flipped: {obs}")
    # random weights leave few clear margins; over many teacher-forced
    # decisions the near-ties may flip, most may not
    check(same.size < 10 or same.mean() >= MIN_DECISION_AGREEMENT,
          f"{what}: too few decisions agree: {obs}")
    return obs


def plain_forward_logits(lm, cfg, prompt):
    """Last-position logits of the plain forward: no cache, no kernel,
    float32 compute on the same (bf16-stored) weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

    ref_cfg = dataclasses.replace(
        cfg, use_flash_attention=False, dtype=jnp.float32, decode=False,
        remat_policy=None, sequence_parallel=False)
    model = LlamaForCausalLM(ref_cfg)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, ids: model.apply({"params": p}, ids))(
            lm.params, jnp.asarray(prompt[None]))
    return np.asarray(logits[0, -1], np.float32)


def serve_phase(sizes: Sizes, seed: int, rehearse: bool, watch, devices) -> dict:
    t0 = time.perf_counter()
    lm, cfg = serving_stack(sizes, tp=1)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    lm.compile()
    trace = make_trace(cfg, seed)
    # warm-up: the trace itself — virtual-time arrivals make the admission
    # groups, and so the (rows, bucket) programs, the same on every pass
    serve(lm, trace, seed)
    warm_s = time.perf_counter() - t0
    warm = watch.counts()
    fused, rep = serve(lm, trace, seed)
    after = watch.counts()
    check(after["compiles"] == warm["compiles"],
          f"{after['compiles'] - warm['compiles']} compilation(s) after "
          f"warm-up: {sorted(lm.compile_ms)}")
    stepwise, _ = serve(lm, trace, seed, fused=False)
    check(fused == stepwise,
          "fused engine streams differ from the stepwise oracle's "
          f"(decision agreement {decision_agreement(stepwise, fused):.4f})")
    prefill = {k: p for k, p in lm._paged_insert.items() if k[1] >= 128}
    check(prefill, "no prefill program at a flash bucket was compiled")
    flash_calls = min(require_mosaic(p.as_text(), 1, f"paged_insert{k}", rehearse)
                      for k, p in prefill.items())

    prompts = probe_prompts(cfg, seed)
    ref_logits, _ = probe(lm, prompts)
    vs_f32 = compare_logits(
        plain_forward_logits(lm, cfg, prompts[0]), ref_logits[0, 0],
        TOL_PREFILL_VS_F32, "prefill logits vs the plain float32 forward")
    emit("serve", layers=cfg.num_layers, hidden=cfg.hidden_size,
         ffn=cfg.intermediate_size, heads=cfg.num_heads, vocab=cfg.vocab_size,
         requests=len(fused), new_tokens=NEW_TOKENS,
         fused_equals_stepwise=True, build_s=round(build_s, 1),
         compile_and_warm_s=round(warm_s, 1),
         compile_ms=lm.compile_ms, compiles_after_warmup=0, cache=after,
         tokens_per_sec=rep["tokens_per_sec"], block_ms_p50=rep["itl_p50_ms"],
         wall_s=rep["wall_s"], host_ops_per_block=rep["host_ops_per_block"],
         flash_custom_calls_in_prefill=flash_calls,
         prefill_vs_float32_forward=vs_f32, memory=memory(devices))


# ---------------------------------------------------------------- training

def train_phase(sizes: Sizes, seed: int, tp: int, rehearse: bool, watch,
                devices, phase: str):
    """Three steps through the example's construction; returns the losses
    and the devices' memory while parameters and optimizer state live."""
    import jax
    import llama2_tp_zero1 as example
    from common import synthetic_lm_batches

    args = argparse.Namespace(
        tiny=False, num_layers=sizes.train_layers, lr=1e-4, warmup_steps=0,
        weight_decay=0.01, checkpoint_dir=None, grad_accum_usteps=1)
    cfg = dataclasses.replace(example.build_config(args, sizes.train_seq),
                              use_flash_attention=True, **sizes.widths)
    batch = next(synthetic_lm_batches(cfg.vocab_size, sizes.train_batch,
                                      sizes.train_seq, seed=seed))
    state, step = example.build_training(args, cfg, tp, batch["ids"], steps=3)
    losses, step_ms = [], []
    for i in range(3):
        if i == 1:
            warm = watch.counts()
        t0 = time.perf_counter()
        state, metrics = step(state, batch, jax.random.key(seed + i + 1))
        losses.append(float(metrics["loss"]))   # host fetch = step finished
        step_ms.append(round((time.perf_counter() - t0) * 1e3, 1))
    after = watch.counts()
    # the text of the program that just ran: the same lowering, found again
    # in the compilation cache where one is on
    text = step.lower(state, batch, jax.random.key(seed)).compile().as_text()
    # forward, dK/dV and dQ kernels of the flash attention
    calls = require_mosaic(text, 3, "train step", rehearse)
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(losses[0] > losses[1] > losses[2], f"loss not falling: {losses}")
    check(after["compiles"] == warm["compiles"],
          "the train step compiled again after step 1")
    coll = collectives(text)
    check(tp == 1 or sum(coll.values()) > 0,
          f"no collective in the TP={tp} train step")
    mem = memory(devices)
    emit(phase, tp=tp, layers=cfg.num_layers, hidden=cfg.hidden_size,
         batch=sizes.train_batch, seq=sizes.train_seq,
         sequence_parallel=cfg.sequence_parallel, losses=losses,
         first_step_compile_s=round((step_ms[0] - step_ms[2]) / 1e3, 1),
         step_ms=step_ms,
         flash_custom_calls=calls, collectives=coll,
         cache=watch.counts(), memory=mem)
    return losses, mem


# --------------------------------------------------------------- four chips

def world(tp: int, devices) -> None:
    from neuronx_distributed_tpu.parallel import mesh

    mesh.destroy_model_parallel()
    mesh.initialize_model_parallel(tensor_model_parallel_size=tp,
                                   devices=devices)


def four_chip_phases(sizes: Sizes, seed: int, rehearse: bool, watch,
                     devices) -> None:
    """TP=4 against a TP=1 world on the same seeds. The golden comes first
    and its world is destroyed before the mesh is rebuilt (the verify
    skill's world discipline)."""
    one = devices[:1]
    world(1, one)
    lm, cfg = serving_stack(sizes, tp=1)
    prompts = probe_prompts(cfg, seed)
    trace = make_trace(cfg, seed)
    ref_logits, ref_toks = probe(lm, prompts)
    ref_streams, _ = serve(lm, trace, seed)
    golden_mem = memory(one)
    del lm
    release()
    golden_losses, golden_train_mem = train_phase(
        sizes, seed, 1, rehearse, watch, one, "train_tp1_golden")
    release()

    world(4, devices)
    lm, cfg = serving_stack(sizes, tp=4)
    logits, _ = probe(lm, prompts, forced=ref_toks)
    vs_tp1 = compare_logits(ref_logits, logits, TOL_TP_LOGITS,
                            "TP=4 logits vs TP=1")
    streams, rep = serve(lm, trace, seed)
    agreement = decision_agreement(ref_streams, streams)
    check(agreement >= MIN_DECISION_AGREEMENT,
          f"TP=4 decision agreement with TP=1 {agreement:.3f}")
    mem = memory(devices)
    divided = shares(mem, golden_mem, rehearse, "serving")
    text = lm._decode.as_text()
    coll = collectives(text)
    check(coll["all-reduce"] > 0, f"no all-reduce in the TP=4 decode: {coll}")
    emit("serve_tp4", layers=cfg.num_layers, hidden=cfg.hidden_size,
         requests=len(streams), vs_tp1=vs_tp1,
         stream_decision_agreement=round(agreement, 4),
         tokens_per_sec=rep["tokens_per_sec"], block_ms_p50=rep["itl_p50_ms"],
         collectives_in_decode=coll, memory=mem, memory_tp1=golden_mem,
         **divided)
    del lm
    release()

    losses, mem = train_phase(sizes, seed, 4, rehearse, watch, devices,
                              "train_tp4")
    rel = max(abs(a - b) / abs(b) for a, b in zip(losses, golden_losses))
    check(rel <= TOL_TP_LOSS,
          f"TP=4 losses {losses} vs TP=1 {golden_losses}: relative {rel:.4f}")
    emit("train_tp4_vs_tp1", losses_tp4=losses, losses_tp1=golden_losses,
         max_relative_difference=round(rel, 5), tolerance=TOL_TP_LOSS,
         **shares(mem, golden_train_mem, rehearse, "training"))


def shares(mem: list, golden: list, rehearse: bool, what: str) -> dict:
    """Weights, pool and optimizer state are divided over the devices, not
    sitting on device 0: the four hold a comparable share, and each well
    under what the one-device world held."""
    if any(m is None for m in mem):
        check(rehearse, f"{what}: a device reports no memory_stats()")
        return {"memory_spread": None}
    used = [m["bytes_in_use"] for m in mem]
    spread = max(used) / max(min(used), 1)
    check(spread <= 1.25, f"{what}: uneven memory over the devices: {used}")
    frac = max(used) / golden[0]["bytes_in_use"]
    check(frac <= 0.5, f"{what}: a TP=4 device holds {frac:.2f} of what the "
          f"TP=1 device held: {used} vs {golden}")
    return {"memory_spread": round(spread, 3),
            "largest_share_of_tp1": round(frac, 3)}


# --------------------------------------------------------------------- main

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny widths on host devices, kernels interpreted")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax

    found = jax.devices()
    platform = found[0].platform
    if args.rehearse == (platform == "tpu"):
        print(f"chip_smoke: JAX found {len(found)} {platform} device(s); "
              + ("--rehearse is for host devices — run without it on the chip"
                 if args.rehearse else
                 "this needs a TPU (--rehearse runs tiny widths on the host)"),
              file=sys.stderr)
        return 2
    if len(found) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(found)} "
              f"{platform} device(s)", file=sys.stderr)
        return 2
    devices = found[:args.chips]
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}

    for p in (ROOT, ROOT / "examples", ROOT / "examples" / "inference",
              ROOT / "examples" / "training"):
        sys.path.insert(0, str(p))
    import jaxlib

    from neuronx_distributed_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    watch = CompileWatch(jax)
    sizes = REHEARSAL if args.rehearse else REAL
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    emit("start", device=device, jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu_version, compile_cache=cache_dir, seed=args.seed,
         serve_layers=sizes.serve_layers, train_layers=sizes.train_layers,
         depth_why=sizes.why)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chip_phases(sizes, args.seed, args.rehearse, watch, devices)
        else:
            world(1, devices)
            serve_phase(sizes, args.seed, args.rehearse, watch, devices)
            release()
            train_phase(sizes, args.seed, 1, args.rehearse, watch, devices,
                        "train")
    except BaseException:
        # the verdict stays the last line of stdout; the traceback and the
        # non-zero exit code follow from the re-raise
        print(json.dumps({"ok": False, "device": device}), flush=True)
        raise
    emit("done", wall_s=round(time.perf_counter() - t0, 1), **watch.counts())
    verdict = {"ok": True, "device": device}
    if args.rehearse:
        verdict["rehearsal"] = True
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
