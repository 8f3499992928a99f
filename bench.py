"""Benchmark: Llama-2-7B training tokens/sec/chip (north-star metric,
BASELINE.json — reference threshold 54k tok/s on 32 NeuronCores = 1687.5
tok/s/core, test/integration/llama2_7B/test_long_seqlen.py:87).

Method (honest, auditable):
  * Run the real training step (bf16 compute, fp32-master AdamW, grad clip,
    full activation remat, Pallas flash attention) at exact Llama-2-7B layer
    dimensions for THREE depths (a full 7B + optimizer state exceeds one
    chip's 16 GB HBM).
  * Least-squares fit step_time(L) = a + b*L and project t_7B = a + 32*b.
    This charges the full per-layer cost 32 times and the fixed cost (embed,
    lm_head, CE loss, optimizer sync, dispatch) once — unlike naive L/32
    scaling, which double-counts the fixed cost 32/L times. Three depths
    over-determine the fit, so a residual is reported (VERDICT r4 weak #2).
  * Noise hardening (VERDICT r4 next #1): the depths are measured in
    INTERLEAVED passes spread across the whole run (direction alternating),
    so machine-state drift between measurement blocks — which lands straight
    in a sequential 2-point fit's slope and is amplified x16 by the
    projection — hits every depth instead of one. Per-depth estimator: min
    over all passes' window means.
  * Timing is synchronized by fetching the loss value to the host before and
    after the timed window (``jax.block_until_ready`` does NOT flush the
    remote-TPU execution stream on this harness; a value fetch does).
  * MFU is reported against the v5e bf16 peak (197 TFLOP/s) using standard
    model FLOPs (6 * matmul_params * tokens + 3.5x causal attention fwd
    FLOPs); remat recompute is NOT counted as useful work, so the number is
    the conventional (conservative) MFU.

Prints exactly one JSON line.
"""

import gc
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

FULL_LAYERS = 32
BASELINE_TOK_S_PER_CHIP = 54000.0 / 32.0  # reference threshold per NeuronCore
V5E_PEAK_BF16 = 197e12


def model_flops_per_step(layers, batch, seq, hidden, intermediate, vocab, n_heads, head_dim):
    """Standard training-step model FLOPs (no remat recompute counted)."""
    per_layer_mm = 4 * hidden * hidden + 3 * hidden * intermediate
    mm_params = layers * per_layer_mm + hidden * vocab  # lm_head; embed is a gather
    tokens = batch * seq
    mm = 6 * mm_params * tokens
    # causal attention: fwd = 2 matmuls * 2*B*H*S^2*D * 1/2 (causal); bwd ~ 2.5x fwd
    attn_fwd = layers * 2 * 2 * batch * n_heads * seq * seq * head_dim * 0.5
    return mm + 3.5 * attn_fwd


def build_step(layers, batch, seq, on_tpu, remat_policy="attention"):
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.trainer import (
        create_train_state,
        initialize_parallel_model,
        initialize_parallel_optimizer,
        make_train_step,
        neuronx_distributed_config,
    )

    if ps.model_parallel_is_initialized():
        ps.destroy_model_parallel()
    cfg = neuronx_distributed_config(
        tensor_parallel_size=1,
        optimizer_config={"zero_one_enabled": False, "grad_clipping": True},
        mixed_precision_config={"use_master_weights": True},
    )
    # bf16 storage + fp32 master in the optimizer (the intended mixed-precision
    # layout; fp32 param storage would duplicate the master copy and force a
    # bf16 cast of every kernel each step). Selective "attention" remat is the
    # reference's own long-seq choice (run_llama_nxd.py:113).
    lcfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_layers=layers, num_heads=32, num_kv_heads=32, max_seq_len=seq,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, use_flash_attention=on_tpu,
        remat_policy=remat_policy,  # blocks: seq-adaptive default
    ) if on_tpu else LlamaConfig(
        vocab_size=1024, hidden_size=256, intermediate_size=512,
        num_layers=layers, num_heads=8, num_kv_heads=8, max_seq_len=seq,
        dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
    )

    ids = jnp.asarray(np.random.RandomState(0).randint(0, lcfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(np.random.RandomState(1).randint(0, lcfg.vocab_size, (batch, seq)))
    model = initialize_parallel_model(cfg, lambda: LlamaForCausalLM(lcfg), ids)
    opt = initialize_parallel_optimizer(cfg, model, learning_rate=1e-4)
    state = create_train_state(model, opt)

    def loss_fn(params, batch_, rng):
        return model.module.apply(
            {"params": params}, batch_["ids"], batch_["labels"], method=LlamaForCausalLM.loss
        )

    step = make_train_step(model, opt, loss_fn)
    return step, state, {"ids": ids, "labels": labels}, lcfg


def timed_steps(step, state, batch_data, steps, windows=1):
    """Per-step time with true host-fetch synchronization at the edges.

    Host-clock timing picks up additive noise from the host's link to the
    chip; we time
    ``windows`` independent windows of ``steps`` steps and report the MIN
    window mean — the standard estimator when noise is strictly additive.
    Returns (best_dt, last_loss).
    """
    state, m = step(state, batch_data, jax.random.key(0))
    float(m["loss"])  # sync: compile + warmup fully retired
    # SECOND warmup: the first post-compile execution is routinely slow too
    # (measured ~8 s at 7B dims vs 0.36 s steady state — post-compile
    # re-layout/donation settling); a single-window caller would otherwise
    # catch it inside the timed window
    state, m = step(state, batch_data, jax.random.key(999983))
    float(m["loss"])
    best = float("inf")
    for w in range(windows):
        t0 = time.perf_counter()
        for i in range(steps):
            state, m = step(state, batch_data, jax.random.key(w * steps + i + 1))
        loss = float(m["loss"])  # sync: drain the execution stream
        best = min(best, (time.perf_counter() - t0) / steps)
        assert np.isfinite(loss), f"non-finite loss {loss}"
    return best, loss


def step_memory_bytes(step, state, batch_data):
    try:
        mem = step.lower(state, batch_data, jax.random.key(0)).compile().memory_analysis()
        return int(mem.temp_size_in_bytes + mem.argument_size_in_bytes)
    except Exception:
        return None


def _fit_line(t: dict):
    """Least-squares (slope, intercept) over {depth: seconds} — the ONE fit
    implementation every projection key derives from."""
    xs = np.asarray(sorted(t), np.float64)
    ys = np.asarray([t[int(x)] for x in xs])
    b, a = np.polyfit(xs, ys, 1)
    return float(b), float(a)


def _depth_fit(t: dict, full: int):
    """Least-squares a + b*L over the measured depths, projected to ``full``.
    Returns (projection_s, max_abs_residual_s) — residual is None when the
    fit degenerated (NaN would make the report line invalid JSON). Falls back
    to conservative naive scaling (fixed cost charged per layer) when noise
    defeats the fit."""
    if not t:
        raise ValueError("_depth_fit needs at least one measured depth")
    xs = np.asarray(sorted(t), np.float64)
    ys = np.asarray([t[int(x)] for x in xs])
    if len(xs) < 2:
        if xs[-1] == 0:
            # only the zero-depth point survived: there is no per-layer
            # signal at all — no projection exists (Infinity would make the
            # report line invalid strict JSON)
            return None, None
        # no fit happened (naive scaling) -> no residual exists to report
        return ys[-1] / xs[-1] * full, None
    b, a = _fit_line(t)
    if b <= 0 or a < 0:
        deepest = int(xs[-1])
        return t[deepest] / deepest * full, None
    resid = float(np.max(np.abs(a + b * xs - ys)))
    return a + full * b, resid


def bench_train(depths=(0, 1, 2, 3), passes=3, steps=4, windows=2, batch=8,
                seq=2048):
    """Interleaved multi-pass train-step depth sweep (header bullet 3).

    Depth choice: L=3 at these dims does NOT fit (≈14 GB of params + fp32
    master/m/v + grads before activations; the attempt is kept in the sweep
    so the artifact records the failure first-hand, then the depth is
    dropped). L=0 is the third REAL point instead: embed -> norm -> head ->
    CE -> optimizer with zero decoder layers — a direct measurement of the
    fit's fixed cost 'a' (embed/head/loss/optimizer-on-those-params/
    dispatch), pinning the intercept the L=1,2 slope previously had to
    infer. The linearity assumption is then CHECKED by the reported
    residual rather than assumed.

    Each visit rebuilds model+optimizer — two 7B-dim models never fit one
    chip's HBM together, and the jit cache does not survive the rebuild, so
    every pass pays retrace+compile per depth (warmup, outside the timed
    windows; XLA's compile cache makes repeat passes cheap). A depth that
    fails is dropped from later passes and recorded; the fit runs over the
    depths that completed.
    Returns {"times": {L: min_window_s}, "mem_L2": bytes|None,
             "skipped": [...], "visits": {L: n}}.
    """
    times = {L: [] for L in depths}
    mem = None
    lcfg = None
    skipped = []
    live = list(depths)
    for p in range(passes):
        order = list(live) if p % 2 == 0 else list(reversed(live))
        for L in order:
            step = state = batch_data = None
            try:
                step, state, batch_data, lcfg = build_step(L, batch, seq, True)
                if mem is None and L == 2:
                    mem = step_memory_bytes(step, state, batch_data)
                dt, _ = timed_steps(step, state, batch_data, steps,
                                    windows=windows)
                times[L].append(dt)
            except Exception as e:  # noqa: BLE001 — drop the depth, keep the sweep
                skipped.append(
                    {"depth": L, "pass": p,
                     "error": f"{type(e).__name__}: {e}"[:120]})
                if L in live:
                    live.remove(L)
            finally:
                del step, state, batch_data
                gc.collect()
    return {
        "times": {L: min(v) for L, v in times.items() if v},
        "mem_L2": mem,
        "lcfg": lcfg,
        "skipped": skipped,
        "visits": {L: len(v) for L, v in times.items() if v},
        "windows_per_visit": windows,
    }


def _prefill_device_window(lm, prompt_len, prompt, iters=3, windows=3):
    """DEVICE-basis prefill cost (VERDICT r4 next #2): ``iters`` prefills
    chained by a data dependency (greedy argmax of the previous call's
    logits, reduced mod 1, folded into the next prompt), so executions
    serialize on-device with NO host read inside the window; the single
    host fetch at the window edge amortizes over ``iters`` — the same
    chained-window technique the decode/speculation metrics use. The chain
    includes the argmax (TTFT's definition samples the first token).
    ``iters`` is kept small: each un-donated call holds a fresh KV cache
    until retired (~0.6 GB at L=12 13B dims)."""
    pf = lm._prefill[prompt_len]
    logits, _ = pf(lm.params, prompt)

    def chain(logits):
        z = (jnp.argmax(logits[0, -1]) % 1).astype(jnp.int32)
        return pf(lm.params, prompt + z)[0]

    logits = chain(logits)
    float(logits[0, 0, 0])        # warm: chain ops compiled + retired
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            logits = chain(logits)
        float(logits[0, 0, 0])    # sync: drain the chain
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def _fused_decode_window(lm, cache, fused_steps=16, calls=2, windows=3):
    """Per-token DEVICE cost of the K-step fused greedy decode program
    (CausalLM.compile_decode_fused): ``calls`` chained program calls per
    window (cache donated through, next-token fed forward), host fetch at
    the edge. Amortizes the per-program dispatch K*calls-fold — the
    counterpart measurement to the step-decode window, isolating how much
    of the step intercept is dispatch (PROFILE.md r5 decode study)."""
    f = lm.compile_decode_fused(fused_steps)
    tok = jnp.zeros((lm.max_batch, 1), jnp.int32)
    rng = jax.random.key(0)
    done = jnp.zeros((lm.max_batch,), bool)
    toks, cache, tok, rng, done = f(lm.params, cache, tok, rng, done)
    int(np.asarray(toks)[0, 0])   # warm + sync
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(calls):
            toks, cache, tok, rng, done = f(lm.params, cache, tok, rng, done)
        int(np.asarray(toks)[-1, 0])
        best = min(best, (time.perf_counter() - t0) / (fused_steps * calls))
    return best


def bench_inference_ttft(prompt_len=2048, depths=(0, 1, 2, 4, 8, 12), trials=15,
                         decode_steps=20, int8_depths=(0, 1, 2, 4, 8)):
    """Llama-2-13B p50 TTFT + decode throughput (north-star metric #2,
    BASELINE.md; reference benchmark.py:43-71 percentile method).

    Same slope method as training: measure prefill/decode at 13B layer dims
    at SIX depths up to L=12, including L=0 — the zero-decoder model
    (embed -> norm -> head -> sampler) whose timings measure the fits'
    fixed costs DIRECTLY (prefill fixed work, per-token non-layer decode
    work: the r5 decode-intercept attribution, VERDICT r4 next #5)
    (on the upper end, VERDICT r3 weak #1: stopping at L=6 meant a
    x7 slope extrapolation that amplified host-link noise until the min-fit and
    p50-fit projections inverted; L=12 is ~8.1 GB bf16 — deep enough to cut
    the extrapolation to x3.3 while leaving headroom for the KV cache and
    the int8 copy on a possibly-fragmented chip),
    least-squares fit a + b*L, project to the full 40 layers. The fit runs
    on THREE bases, all reported: per-depth MIN (additive-noise estimator
    for host-link latency spikes), per-depth p50 (the metric's own
    host-inclusive definition), and per-depth DEVICE (chained prefill
    windows — no harness RTT inside; VERDICT r4 next #2). The fit residual
    quantifies how linear the measurements actually were. Decode is
    measured on the step program AND the 16-step fused program
    (``compile_decode_fused`` — isolates the dispatch share of the step
    intercept), each additionally with int8 weight-only quantized params at
    FOUR ``int8_depths`` (the bf16 model is freed before the int8 copy is
    built so only the quantize transient holds both). A bf16-phase failure
    (OOM on a fragmented chip) is recorded in ``ttft_skipped_depths`` and
    stops the sweep; an int8-phase failure is recorded in
    ``int8_skipped_depths`` and the sweep continues — the depth's bf16
    points are already banked (ADVICE r4 low #3).
    TTFT is end-to-end: prompt in, first sampled token fetched on the host.
    """
    import gc

    from neuronx_distributed_tpu.inference import CausalLM
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.quantization.core import quantize_params
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_model, neuronx_distributed_config,
    )

    FULL = 40  # Llama-2-13B depth
    prefill_min, prefill_p50, prefill_dev = {}, {}, {}
    decode_t, decode_int8_t = {}, {}
    decode_fused_t, decode_int8_fused_t = {}, {}
    skipped, int8_skipped = [], []
    gc.collect()
    # harness transport constant: the host->TPU dispatch + value-fetch round
    # trip for a trivial program. Every per-call latency above (and the fit
    # intercept) includes one of these; a real deployment's serving stack
    # has its own dispatch path, so report it for decomposition.
    noop = jax.jit(lambda x: x + 1).lower(jnp.zeros((1,), jnp.int32)).compile()
    z = jnp.zeros((1,), jnp.int32)
    int(noop(z)[0])
    rtt = []
    for _ in range(30):
        t0 = time.perf_counter()
        int(noop(z)[0])
        rtt.append(time.perf_counter() - t0)
    harness_rtt_ms = {
        "harness_rtt_ms_p50": round(float(np.percentile(rtt, 50)) * 1e3, 2),
        "harness_rtt_ms_min": round(float(np.min(rtt)) * 1e3, 2),
    }
    # chained-dispatch floor: per-call cost of the same trivial program when
    # calls are chained with no host read inside the window — the ASYNC
    # dispatch cost every chained device window (decode/spec/fused) pays per
    # program call. This is the measured floor of the step-decode fit
    # intercept (PROFILE.md r5 decode-intercept attribution).
    y = noop(z)
    int(y[0])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(20):
            y = noop(y)
        int(y[0])
        best = min(best, (time.perf_counter() - t0) / 20)
    harness_rtt_ms["harness_dispatch_chained_ms"] = round(best * 1e3, 3)
    def decode_window(lm_, cache_, windows=3):
        # min over independent windows: one host-link latency spike inside a
        # single window once swung the int8 projection 22 -> 83 ms/tok
        tok = jnp.zeros((1, 1), jnp.int32)
        logits_, cache_ = lm_._decode(lm_.params, cache_, tok)
        float(logits_[0, 0, 0])
        best = float("inf")
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(decode_steps):
                logits_, cache_ = lm_._decode(lm_.params, cache_, tok)
            float(logits_[0, 0, 0])
            best = min(best, (time.perf_counter() - t0) / decode_steps)
        return best

    for layers in depths:
        # --- bf16 phase: a failure here means deeper depths won't fit
        # either -> record and stop the sweep ---------------------------
        lm = model = cache = logits = None
        try:
            if ps.model_parallel_is_initialized():
                ps.destroy_model_parallel()
            cfg = neuronx_distributed_config(tensor_parallel_size=1)
            lcfg = LlamaConfig(
                vocab_size=32000, hidden_size=5120, intermediate_size=13824,
                num_layers=layers, num_heads=40, num_kv_heads=40,
                max_seq_len=prompt_len + 512, dtype=jnp.bfloat16,
                param_dtype=jnp.bfloat16, use_flash_attention=True,
                remat_policy=None,  # blocks: seq-adaptive default
            )
            from neuronx_distributed_tpu.kernels.flash_attn import flash_supported

            assert prompt_len >= 128 and flash_supported(
                prompt_len, lcfg.max_seq_len,
                *lcfg.blocks_for(prompt_len, lcfg.max_seq_len)
            ), "TTFT config must exercise the flash-prefill path, not dense fallback"
            ids = jnp.zeros((1, 8), jnp.int32)
            model = initialize_parallel_model(cfg, lambda: LlamaForCausalLM(lcfg), ids)
            lm = CausalLM(lcfg, model.params, LlamaForCausalLM,
                          buckets=(prompt_len,), max_batch=1).compile()
            prompt = jnp.asarray(
                np.random.RandomState(0).randint(1, 32000, (1, prompt_len)), jnp.int32)

            # HOST-basis TTFT: prefill -> last-token logits -> greedy token
            # fetched on host (includes one harness RTT per trial).
            # 3 UNTIMED warmups first: the first executions of a fresh
            # program pay one-off program-upload costs that once made
            # L=1 measure SLOWER than L=2 (an interleaved probe confirmed
            # warm-state L1 < L2 at the physical ~13 ms/layer slope) —
            # min-over-trials cannot recover from a systematically cold
            # window.
            for _ in range(3):
                logits, cache = lm._prefill[prompt_len](lm.params, prompt)
                int(jnp.argmax(logits[0, -1]))
            ts = []
            for _ in range(trials):
                t0 = time.perf_counter()
                logits, cache = lm._prefill[prompt_len](lm.params, prompt)
                int(jnp.argmax(logits[0, -1]))  # host fetch = sync
                ts.append(time.perf_counter() - t0)
            prefill_min[layers] = float(np.min(ts))
            prefill_p50[layers] = float(np.percentile(ts, 50))
            # DEVICE-basis TTFT: chained prefills, host fetch amortized
            prefill_dev[layers] = _prefill_device_window(lm, prompt_len, prompt)

            decode_t[layers] = decode_window(lm, cache)
            _, cache = lm._prefill[prompt_len](lm.params, prompt)
            decode_fused_t[layers] = _fused_decode_window(lm, cache)
            cache = None
        except Exception as e:  # noqa: BLE001 — deeper depths won't fit either
            skipped.append({"depth": layers, "error": f"{type(e).__name__}: {e}"[:120]})
            del lm, model, cache, logits
            gc.collect()
            break

        # --- int8 phase: records failures under its OWN key and keeps the
        # sweep going — the bf16 numbers above are already banked, and
        # deeper bf16 depths may still fit (ADVICE r4 low #3) -------------
        if layers in int8_depths:
            lm8 = cache8 = q_params = None
            try:
                # int8-in-HBM serving: quantized leaves feed the model
                # directly; the layers dequantize in-scan. Free the bf16
                # model FIRST (only the quantize transient holds both
                # copies) so deep int8 depths fit.
                q_params = quantize_params(model.params)
                del lm, model, cache, logits
                lm = model = cache = logits = None
                gc.collect()
                lm8 = CausalLM(lcfg, q_params, LlamaForCausalLM,
                               buckets=(prompt_len,), max_batch=1)
                lm8.compile()
                _, cache8 = lm8._prefill[prompt_len](lm8.params, prompt)
                decode_int8_t[layers] = decode_window(lm8, cache8)
                _, cache8 = lm8._prefill[prompt_len](lm8.params, prompt)
                decode_int8_fused_t[layers] = _fused_decode_window(lm8, cache8)
            except Exception as e:  # noqa: BLE001 — int8-only failure
                int8_skipped.append(
                    {"depth": layers, "error": f"{type(e).__name__}: {e}"[:120]})
            finally:
                del lm8, cache8, q_params
        del lm, model, cache, logits
        gc.collect()

    if not prefill_min:
        # every depth failed before measuring — surface the root causes
        # instead of _depth_fit's empty-dict ValueError masking them
        return {"ttft_skipped_depths": skipped, **harness_rtt_ms}
    ttft_min_proj, ttft_min_resid = _depth_fit(prefill_min, FULL)
    ttft_p50_proj, ttft_p50_resid = _depth_fit(prefill_p50, FULL)
    ttft_dev_proj, ttft_dev_resid = (
        _depth_fit(prefill_dev, FULL) if prefill_dev else (None, None))
    decode_proj, decode_resid = (
        _depth_fit(decode_t, FULL) if decode_t else (None, None))
    ms = lambda v: None if v is None else round(v * 1e3, 2)  # noqa: E731
    report = {
        # host-basis TTFT embeds one harness RTT (~80-124 ms) in the fit
        # intercept; DEVICE basis (chained windows) is the framework's own
        # prefill cost — a real serving stack pays neither this harness's
        # transport nor its dispatch pattern (VERDICT r4 next #2: report both bases)
        "ttft_ms_13b_projected_minfit": ms(ttft_min_proj),
        "ttft_ms_13b_projected_p50fit": ms(ttft_p50_proj),
        "ttft_device_ms_13b_projected": ms(ttft_dev_proj),
        "ttft_fit_residual_ms": ms(ttft_min_resid),
        "ttft_p50_fit_residual_ms": ms(ttft_p50_resid),
        "ttft_device_fit_residual_ms": ms(ttft_dev_resid),
        "decode_ms_per_token_13b_projected": ms(decode_proj),
        "decode_fit_residual_ms": ms(decode_resid),
        # estimator note: r3 changed decode timing from one window's mean to
        # MIN over 3 window means (same additive-noise rationale as the
        # prefill minfit keys) — do not read cross-round decode deltas as
        # pure model speedup without checking this basis
        "decode_basis": "min_of_3_window_means",
        "ttft_prompt_len": prompt_len,
        **harness_rtt_ms,
        "ttft_fit_depths": list(map(int, sorted(prefill_min))),
        "ttft_min_ms_measured": {str(k): ms(v) for k, v in sorted(prefill_min.items())},
        "ttft_p50_ms_measured": {str(k): ms(v) for k, v in sorted(prefill_p50.items())},
        "ttft_device_ms_measured": {str(k): ms(v) for k, v in sorted(prefill_dev.items())},
        "decode_ms_measured": {str(k): ms(v) for k, v in sorted(decode_t.items())},
    }
    if decode_fused_t:
        fused_proj, _ = _depth_fit(decode_fused_t, FULL)
        report.update({
            # 16-step fused greedy decode (one program per 16 tokens):
            # amortizes the per-program dispatch that dominates the step
            # fit's intercept — the serving fast path for greedy decode
            "decode_fused16_ms_per_token_13b_projected": ms(fused_proj),
            "decode_fused16_ms_measured": {
                str(k): ms(v) for k, v in sorted(decode_fused_t.items())},
        })
    if skipped:
        report["ttft_skipped_depths"] = skipped
    if int8_skipped:
        # int8-phase-only failures: the same depth's bf16 TTFT/decode points
        # above are real and feed the fits (ADVICE r4 low #3)
        report["int8_skipped_depths"] = int8_skipped
    if ttft_min_proj is not None and ttft_p50_proj is not None \
            and ttft_min_proj > ttft_p50_proj:
        # a min-based fit should lower-bound a p50-based one; if not, the
        # depth sweep was too noisy to trust — say so in the artifact
        # (VERDICT r3 weak #1 requires the ordering or a written explanation)
        report["ttft_fit_note"] = (
            "min-fit projection exceeds p50-fit: per-depth min windows were "
            "noisier than medians this run (host-link drift); prefer the "
            "p50 fit, which is the metric's own basis")
    if decode_int8_t:  # int8_depths need not intersect depths
        decode8_proj, decode8_resid = _depth_fit(decode_int8_t, FULL)
        report.update({
            "decode_ms_per_token_13b_projected_int8": ms(decode8_proj),
            "decode_int8_fit_residual_ms": ms(decode8_resid),
            "decode_int8_ms_measured": {
                str(k): ms(v) for k, v in sorted(decode_int8_t.items())},
        })
        if decode8_proj is not None:
            report["decode_tokens_per_sec_13b_int8"] = round(1.0 / decode8_proj, 1)
    if decode_int8_fused_t:
        fused8_proj, _ = _depth_fit(decode_int8_fused_t, FULL)
        report.update({
            "decode_fused16_ms_per_token_13b_projected_int8": ms(fused8_proj),
            "decode_int8_fused16_ms_measured": {
                str(k): ms(v) for k, v in sorted(decode_int8_fused_t.items())},
        })
        if fused8_proj is not None:
            report["decode_fused16_tokens_per_sec_13b_int8"] = round(
                1.0 / fused8_proj, 1)
    return report


def bench_speculation(target_layers=8, draft_layers=2, num_draft=4,
                      prompt_len=128):
    """Speculative-decoding metrics at 13B layer dims (VERDICT r3 missing #4;
    reference examples/inference/runner.py:454-530 percentile report).

    What is measured and why it is shaped this way:

    * per-submodel DEVICE cost via chained windows (no host read inside):
      ``spec_draft_propose_ms`` (one γ-token proposal scan on the
      ``draft_layers``-deep draft) and ``spec_verify_chunk_ms`` (the
      target's γ+1-token chunked verify). An end-to-end tok/s over THIS
      harness's host loop is ~5 host round-trips/round ≈ hundreds of ms
      of pure transport — it would benchmark the transport, not the framework
      (r4 first attempt measured exactly that and is the reason for this
      design);
    * acceptance plumbing via a short self-draft run (draft == target):
      greedy self-speculation must accept EVERYTHING, so
      ``spec_acceptance_selfdraft`` == 1.0 is a correctness gate, and with
      random init weights a truncated draft accepts ~nothing — a trained
      draft checkpoint is what sets real-world α, not the framework;
    * the speculation economics those numbers imply:
      ``spec_speedup_alpha1`` = (γ+1) · plain_decode_ms / round_device_ms —
      the ceiling at full acceptance; linear in α down to
      ``1/round · plain`` at α = 0.
    """
    import dataclasses
    import gc

    from neuronx_distributed_tpu.inference import CausalLM
    from neuronx_distributed_tpu.inference.speculative import (
        _make_proposer,
        speculative_generate,
    )
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_model, neuronx_distributed_config,
    )

    if ps.model_parallel_is_initialized():
        ps.destroy_model_parallel()
    cfg = neuronx_distributed_config(tensor_parallel_size=1)
    lcfg = LlamaConfig(
        vocab_size=32000, hidden_size=5120, intermediate_size=13824,
        num_layers=target_layers, num_heads=40, num_kv_heads=40,
        max_seq_len=prompt_len + 256,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        use_flash_attention=True, remat_policy=None,
    )
    ids = jnp.zeros((1, 8), jnp.int32)
    model = initialize_parallel_model(cfg, lambda: LlamaForCausalLM(lcfg), ids)
    lm = CausalLM(lcfg, model.params, LlamaForCausalLM,
                  buckets=(prompt_len,), max_batch=1).compile()
    d_cfg = dataclasses.replace(lcfg, num_layers=draft_layers)
    d_params = jax.tree.map(
        lambda p: p[:draft_layers] if (
            hasattr(p, "shape") and p.ndim > 0 and p.shape[0] == target_layers
        ) else p, model.params)
    draft = CausalLM(d_cfg, d_params, LlamaForCausalLM,
                     buckets=(prompt_len,), max_batch=1).compile()
    prompt = np.random.RandomState(0).randint(
        1, 32000, (1, prompt_len)).astype(np.int32)

    def window(fn, *state, iters=10, windows=3):
        """min-over-windows of a chained device program; ``fn(*state)`` must
        return the next state with the SAME structure. Sync at window edges
        is a host VALUE FETCH of the first output — block_until_ready does
        not flush the remote-TPU stream on this harness (file header)."""
        sync = lambda st: np.asarray(st[0]).ravel()[0]  # noqa: E731
        state = fn(*state)
        sync(state)
        best = float("inf")
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(iters):
                state = fn(*state)
            sync(state)
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    # draft proposer: one γ-token scan per call, cache chained
    proposer = _make_proposer(draft, num_draft, greedy=True, temperature=1.0)
    _, d_cache0 = draft._prefill[prompt_len](draft.params, jnp.asarray(prompt))
    last = jnp.zeros((1,), jnp.int32)

    def prop_step(toks, cache):
        t2, _, c2 = proposer(draft.params, cache, last, jax.random.key(0))
        return t2, c2

    draft_ms = window(prop_step, jnp.zeros((num_draft, 1), jnp.int32), d_cache0) * 1e3

    # target chunked verify: γ+1 tokens against the cache
    def chunk_fn(params, cache, ids_):
        logits, mut = lm.model.apply(
            {"params": lm._resolve(params), "cache": cache}, ids_,
            mutable=["cache"])
        return logits, mut["cache"]

    _, t_cache0 = lm._prefill[prompt_len](lm.params, jnp.asarray(prompt))
    chunk_ids = jnp.zeros((1, num_draft + 1), jnp.int32)
    chunk_c = jax.jit(chunk_fn, donate_argnums=(1,)).lower(
        lm.params, t_cache0, chunk_ids).compile()

    def verify_step(logits, cache):
        return chunk_c(lm.params, cache, chunk_ids)

    verify_ms = window(verify_step, jnp.zeros((1,)), t_cache0) * 1e3

    # plain decode at the same target depth, chained
    _, p_cache = lm._prefill[prompt_len](lm.params, jnp.asarray(prompt))
    tok = jnp.zeros((1, 1), jnp.int32)

    def plain_step(logits, cache):
        return lm._decode(lm.params, cache, tok)

    plain_ms = window(plain_step, jnp.zeros((1,)), p_cache, iters=20) * 1e3

    # acceptance plumbing: greedy self-draft must accept everything
    self_res = speculative_generate(lm, lm, prompt, max_new_tokens=12,
                                    num_draft=num_draft, greedy=True,
                                    rng=jax.random.key(0))
    round_ms = draft_ms + verify_ms

    # Medusa submodels at the same target depth (reference speculative
    # benchmark covers the medusa path too): the tree verify (m-node cached
    # forward under the tree mask) and the accepted-chunk replay, chained.
    # Head QUALITY is a training question (random heads accept ~nothing, and
    # medusa's greedy posterior keeps output exact regardless) — the device
    # cost of the machinery is the framework metric.
    medusa = {}
    try:
        from neuronx_distributed_tpu.inference.medusa import (
            DEFAULT_CHOICES,
            MedusaLlamaForCausalLM,
            generate_medusa_buffers,
        )
        from flax.core import meta

        buffers = generate_medusa_buffers(DEFAULT_CHOICES)
        m_nodes, depth = int(buffers["num_nodes"]), int(buffers["depth"])
        import dataclasses as _dc

        mcfg = _dc.replace(lcfg, decode=True, sequence_parallel=False,
                           remat_policy=None)
        mm = MedusaLlamaForCausalLM(mcfg, num_medusa_heads=2)
        # medusa-head shapes depend only on hidden/vocab: init a 1-layer
        # throwaway trunk for them (a full-depth init would allocate a ~6 GB
        # transient at the bench's most memory-pressured moment), then use
        # the target's real trunk + head
        mm1 = MedusaLlamaForCausalLM(_dc.replace(mcfg, num_layers=1),
                                     num_medusa_heads=2)
        mparams = meta.unbox(jax.jit(
            lambda: mm1.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))
        )())["params"]
        for k, v in model.params.items():
            mparams[k] = v
        chunk_mask = jnp.asarray(buffers["attn_mask"])
        chunk_pos = jnp.asarray(buffers["position_ids"])

        @jax.jit
        def prefill_m(params, ids_):
            (logits, _), mut = mm.apply({"params": params}, ids_, None,
                                        mutable=["cache"])
            return logits, mut["cache"]

        _, m_cache = prefill_m(mparams, jnp.asarray(prompt))

        def tree_fn(params, cache, toks):
            (logits, _), mut = mm.apply(
                {"params": params, "cache": cache}, toks,
                (chunk_mask, chunk_pos), heads=False, mutable=["cache"])
            return logits, mut["cache"]

        tree_c = jax.jit(tree_fn, donate_argnums=(1,)).lower(
            mparams, m_cache, jnp.zeros((1, m_nodes), jnp.int32)).compile()
        tree_toks = jnp.zeros((1, m_nodes), jnp.int32)
        medusa["spec_medusa_tree_ms"] = round(window(
            lambda lg, c: tree_c(mparams, c, tree_toks),
            jnp.zeros((1,)), m_cache) * 1e3, 2)

        def replay_fn(params, cache, toks):
            (logits, _), mut = mm.apply(
                {"params": params, "cache": cache}, toks, None,
                mutable=["cache"])
            return logits, mut["cache"]

        _, m_cache2 = prefill_m(mparams, jnp.asarray(prompt))
        replay_c = jax.jit(replay_fn, donate_argnums=(1,)).lower(
            mparams, m_cache2, jnp.zeros((1, depth + 1), jnp.int32)).compile()
        rt = jnp.zeros((1, depth + 1), jnp.int32)
        medusa["spec_medusa_replay_ms"] = round(window(
            lambda lg, c: replay_c(mparams, c, rt),
            jnp.zeros((1,)), m_cache2) * 1e3, 2)
        medusa["spec_medusa_tree_nodes"] = m_nodes
        # tree_ms ~= replay_ms above (both are one cached forward over a
        # handful of tokens): medusa's whole win is ACCEPTANCE LENGTH, so
        # measure it (VERDICT r4 next #4). Heads are lm_head-TIED (the
        # ResBlock W is zero-init, so head i exactly predicts the base
        # next-token distribution rather than offset i+2): untrained but
        # non-degenerate — acceptance occurs exactly where the model's own
        # greedy continuation repeats tokens, and the full tree machinery
        # (candidate pool, masked verify, posterior, compacting replay)
        # runs under a measured, not assumed, acceptance.
        from neuronx_distributed_tpu.inference.medusa import medusa_generate

        mt_params = dict(mparams)
        for i in range(2):
            mt_params[f"medusa_head_{i}"] = mparams["lm_head"]
        mres = medusa_generate(lcfg, mt_params, prompt, max_new_tokens=24,
                               num_medusa_heads=2, bucket=prompt_len)
        medusa["spec_medusa_acceptance_measured"] = mres.stats["acceptance_rate"]
        medusa["spec_medusa_tokens_per_round_measured"] = mres.stats["tokens_per_round"]
        # tied heads accept only where the greedy continuation repeats a
        # token; a repeated-token prompt makes that regime reachable so the
        # accept-length>0 path is exercised measured, not assumed
        rep_prompt = np.full((1, prompt_len), 777, np.int32)
        mres2 = medusa_generate(lcfg, mt_params, rep_prompt, max_new_tokens=24,
                                num_medusa_heads=2, bucket=prompt_len)
        medusa["spec_medusa_acceptance_repetitive"] = mres2.stats["acceptance_rate"]
        medusa["spec_medusa_tokens_per_round_repetitive"] = mres2.stats["tokens_per_round"]
        medusa["spec_medusa_acceptance_basis"] = (
            "lm_head-tied untrained heads — a measured lower bound; trained "
            "heads raise acceptance, not the per-round device cost above; "
            "_repetitive row uses a repeated-token prompt")
        del mparams, mt_params, m_cache, m_cache2, tree_c, replay_c
    except Exception as e:  # medusa numbers are additive, never fatal
        medusa["spec_medusa_error"] = f"{type(e).__name__}: {e}"[:120]
    # --- REAL acceptance (VERDICT r4 next #4): the int8-quantized copy of
    # the SAME weights drafts for the bf16 target. Per-channel int8 rounding
    # perturbs every logit, so the draft's greedy chain genuinely diverges
    # from the target's — a measured alpha in (0,1) with zero training, and
    # the measured tokens/round prices the speculation economics instead of
    # the alpha=1 extrapolation. ---------------------------------------
    real = {}
    lm8 = None
    try:
        from neuronx_distributed_tpu.quantization.core import quantize_params

        q_params = quantize_params(model.params)
        lm8 = CausalLM(lcfg, q_params, LlamaForCausalLM,
                       buckets=(prompt_len,), max_batch=1).compile()
        res8 = speculative_generate(lm, lm8, prompt, max_new_tokens=48,
                                    num_draft=num_draft, greedy=True,
                                    rng=jax.random.key(3))
        st = res8.stats
        real["spec_acceptance_real_int8draft"] = st["acceptance_rate"]
        real["spec_tokens_per_round_real_int8draft"] = st["tokens_per_round"]
        real["spec_rounds_real_int8draft"] = st["rounds"]
        # device-basis economics at the MEASURED acceptance: full-depth int8
        # draft propose window + the target's verify window
        proposer8 = _make_proposer(lm8, num_draft, greedy=True, temperature=1.0)
        _, d8_cache = lm8._prefill[prompt_len](lm8.params, jnp.asarray(prompt))

        def prop8_step(toks, cache):
            t2, _, c2 = proposer8(lm8.params, cache, last, jax.random.key(0))
            return t2, c2

        draft8_ms = window(prop8_step, jnp.zeros((num_draft, 1), jnp.int32),
                           d8_cache) * 1e3
        round8_ms = draft8_ms + verify_ms
        real["spec_draft_propose_ms_int8_fulldepth"] = round(draft8_ms, 2)
        real["spec_round_device_ms_int8draft"] = round(round8_ms, 2)
        real["spec_speedup_measured_int8draft"] = round(
            st["tokens_per_round"] * plain_ms / round8_ms, 3)
        real["spec_speedup_measured_basis"] = (
            "measured tokens/round x plain-decode device window / "
            "(int8-draft propose + verify device windows); same-depth draft "
            "prices the acceptance machinery, not a small-draft deployment")
        del proposer8, d8_cache, q_params
    except Exception as e:  # noqa: BLE001 — additive, never fatal
        real["spec_real_acceptance_error"] = f"{type(e).__name__}: {e}"[:120]
    finally:
        del lm8
        gc.collect()

    # --- fused single-program speculation (the tentpole serving fast path):
    # the ENTIRE round — propose scan, chunked verify, accept/rollback,
    # cache compaction — lives in one XLA program, R rounds per dispatch.
    # Draft = the genuinely small 2-layer copy, int8-quantized (VERDICT r5
    # next #3: the configuration that should actually win) ------------------
    fusedspec = {}
    try:
        from neuronx_distributed_tpu.inference.causal_lm import _set_cache_index
        from neuronx_distributed_tpu.inference.speculative import (
            _compile_block,
            speculative_decode_fused,
        )
        from neuronx_distributed_tpu.quantization.core import quantize_params

        R = 8
        draft8 = CausalLM(d_cfg, quantize_params(d_params), LlamaForCausalLM,
                          buckets=(prompt_len,), max_batch=1).compile()
        # device window over the R-round block program: chained calls (caches
        # donated through), ONE host fetch at the window edge — per-round
        # device cost with the dispatch amortized R-fold
        _, t_cf = lm._prefill[prompt_len](lm.params, jnp.asarray(prompt))
        _, d_cf = draft8._prefill[prompt_len](draft8.params, jnp.asarray(prompt))
        lens0 = jnp.asarray([prompt_len], jnp.int32)
        t_cf = _set_cache_index(t_cf, lens0)
        d_cf = _set_cache_index(d_cf, lens0)
        rng0 = jax.random.key(0)
        # max_new huge => rounds never freeze inside the timing window
        block = _compile_block(lm, draft8, t_cf, d_cf, rng0, num_draft, R,
                               True, 1.0, None, 0, 1 << 30)
        state = (t_cf, d_cf, jnp.int32(1), jnp.int32(prompt_len),
                 jnp.int32(1), jnp.bool_(False), rng0)

        def blk_step(toks, *st):
            out_ = block(lm.params, draft8.params, *st)
            return (out_[7],) + out_[:7]

        blk_ms = window(blk_step, jnp.zeros((R, num_draft + 1), jnp.int32),
                        *state, iters=3) * 1e3
        fusedspec["spec_fused_rounds_per_block"] = R
        fusedspec["spec_fused_block_device_ms"] = round(blk_ms, 2)
        fusedspec["spec_fused_round_device_ms"] = round(blk_ms / R, 2)
        # end-to-end wall clock (prefill + blocks + host reads), warmed: the
        # dispatch amortization is the whole point, so measure it end to end
        n_tok = 64
        # warmups must hit the SAME static configs as the timed runs (the
        # fused-block key includes max_new_tokens; generate only enters the
        # fused-16 path when >16 tokens remain) or the timed window would
        # pay the XLA compile it claims to amortize
        speculative_decode_fused(lm, draft8, prompt, max_new_tokens=n_tok,
                                 num_draft=num_draft, rounds_per_block=R)
        t0 = time.perf_counter()
        fres = speculative_decode_fused(lm, draft8, prompt,
                                        max_new_tokens=n_tok,
                                        num_draft=num_draft,
                                        rounds_per_block=R)
        spec_tps = int(fres.lengths[0]) / (time.perf_counter() - t0)
        lm.generate(prompt, max_new_tokens=24, fused_chunk=16)  # warm plain
        t0 = time.perf_counter()
        lm.generate(prompt, max_new_tokens=n_tok, fused_chunk=16)
        plain_tps = n_tok / (time.perf_counter() - t0)
        fusedspec["spec_fused_tokens_per_sec_int8draft2L"] = round(spec_tps, 1)
        fusedspec["spec_fused_plain16_tokens_per_sec"] = round(plain_tps, 1)
        fusedspec["spec_speedup_fused_int8draft2L"] = round(
            spec_tps / plain_tps, 3)
        fusedspec["spec_fused_acceptance_int8draft2L"] = (
            fres.stats or {}).get("acceptance_rate")
        fusedspec["spec_fused_block_calls"] = (fres.stats or {}).get(
            "fused_block_calls")
        fusedspec["spec_speedup_fused_basis"] = (
            "end-to-end wall clock, warmed: fused speculation (2-layer int8 "
            "draft, R=8 rounds/dispatch) vs fused-16 plain greedy decode, "
            "both ~2 host ops per device program")
        del draft8, t_cf, d_cf, block, state
    except Exception as e:  # noqa: BLE001 — additive, never fatal
        fusedspec["spec_fused_error"] = f"{type(e).__name__}: {e}"[:120]
    gc.collect()

    out = {
        "spec_target_layers": target_layers,
        "spec_draft_layers": draft_layers,
        "spec_num_draft": num_draft,
        "spec_draft_propose_ms": round(draft_ms, 2),
        "spec_verify_chunk_ms": round(verify_ms, 2),
        "spec_round_device_ms": round(round_ms, 2),
        "spec_plain_decode_ms": round(plain_ms, 2),
        "spec_acceptance_selfdraft": (self_res.stats or {}).get("acceptance_rate"),
        "spec_selfdraft_round_ms_p50": (self_res.stats or {}).get("round_ms_p50"),
        "spec_selfdraft_round_ms_p90": (self_res.stats or {}).get("round_ms_p90"),
        # the selfdraft round times are a HOST loop (~5 round trips per
        # round, p90 once included multi-second transport stalls) — they
        # validate acceptance plumbing, not speed; device economics are the
        # *_device_ms keys (VERDICT r4 weak #5: label transport-dominated
        # artifacts as such)
        "spec_selfdraft_basis": "host-loop; transport-dominated",
        # ceiling at full acceptance; scales ~linearly down with alpha
        "spec_speedup_alpha1": round((num_draft + 1) * plain_ms / round_ms, 3),
        "spec_speedup_alpha0": round(plain_ms / round_ms, 3),
        **real,
        **medusa,
    }
    del lm, draft, model, d_cache0, t_cache0, p_cache, chunk_c
    gc.collect()
    return out


def bench_serving(layers=8, prompt_len=128, max_batch=4, fused_steps=16):
    """Continuous-batching serving metrics at 13B layer dims (ISSUE 2
    tentpole evidence). Three questions, one model build:

    * ``serve_insert_ms_1slot`` / ``serve_insert_ms_4slot`` — cost of the
      RIGHT-SIZED insert (prefill only the inserted rows at their own batch
      width + per-slot ``dynamic_update_slice`` scatter), next to
      ``serve_insert_fullwidth_ms_1slot`` — the pre-PR2 path (full
      ``max_batch``-wide prefill + whole-cache ``jnp.where`` merge, measured
      as it was: eager per-leaf merge). The 1-slot gap is the insert-cost
      scaling claim.
    * ``serve_fused_round_device_ms`` — chained device window over the
      fused session program (K steps for the whole slot pool per call,
      cache donated through, one fetch at the window edge), with
      ``serve_fused_ms_per_token`` = round/K and the honesty ratio
      ``serve_fused_vs_generate_fused16`` against ``compile_decode_fused``
      at the SAME depth/batch — continuous batching must not give back the
      dispatch amortization (acceptance: ratio <= ~1.15).
    * ``serve_tokens_per_sec_cb`` — end-to-end engine throughput over a
      synthetic arrival trace (admission queue, bucketed right-sized
      inserts, retire-on-EOS), warmed, wall clock.
    """
    import gc

    from neuronx_distributed_tpu.inference import CausalLM, ServeEngine
    from neuronx_distributed_tpu.inference.causal_lm import _merge_cache_slots
    from neuronx_distributed_tpu.inference.engine import run_trace, synthetic_trace
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_model, neuronx_distributed_config,
    )

    if ps.model_parallel_is_initialized():
        ps.destroy_model_parallel()
    cfg = neuronx_distributed_config(tensor_parallel_size=1)
    lcfg = LlamaConfig(
        vocab_size=32000, hidden_size=5120, intermediate_size=13824,
        num_layers=layers, num_heads=40, num_kv_heads=40,
        max_seq_len=prompt_len + 256, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, use_flash_attention=True, remat_policy=None,
    )
    ids = jnp.zeros((1, 8), jnp.int32)
    model = initialize_parallel_model(cfg, lambda: LlamaForCausalLM(lcfg), ids)
    lm = CausalLM(lcfg, model.params, LlamaForCausalLM,
                  buckets=(prompt_len,), max_batch=max_batch).compile()
    rs = np.random.RandomState(0)
    prompts = rs.randint(1, 32000, (max_batch, prompt_len)).astype(np.int32)

    def sync_cache(session):
        # the insert scatter is async; force it by fetching one element of a
        # cache leaf (logits alone would not order after the scatter)
        leaf = jax.tree_util.tree_leaves(session.cache)[0]
        np.asarray(leaf.ravel()[0])

    def min_ms(fn, trials=8):
        fn()  # warm (compile outside the window)
        best = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    out = {}
    session = lm.start_session()

    def insert_1():
        lm.insert(session, [0], prompts[:1])
        sync_cache(session)

    def insert_4():
        lm.insert(session, np.arange(max_batch), prompts)
        sync_cache(session)

    out["serve_insert_ms_1slot"] = round(min_ms(insert_1), 2)
    out["serve_insert_ms_4slot"] = round(min_ms(insert_4), 2)

    def insert_fullwidth_1():
        # the pre-right-sizing insert, verbatim: max_batch-wide prefill +
        # eager whole-cache where-merge
        ids_ = np.zeros((max_batch, prompt_len), np.int32)
        ids_[0] = prompts[0]
        _, fresh = lm._prefill[prompt_len](lm.params, jnp.asarray(ids_))
        sel = np.zeros((max_batch,), bool)
        sel[0] = True
        new_len = np.zeros((max_batch,), np.int32)
        new_len[0] = prompt_len
        session.cache = _merge_cache_slots(session.cache, fresh,
                                           jnp.asarray(sel), jnp.asarray(new_len))
        sync_cache(session)

    out["serve_insert_fullwidth_ms_1slot"] = round(min_ms(insert_fullwidth_1), 2)

    # fused session decode: chained device window, all slots live
    fused = lm.compile_session_decode_fused(fused_steps)
    lm.insert(session, np.arange(max_batch), prompts)
    state = (session.cache, jnp.zeros((max_batch, 1), jnp.int32),
             jax.random.split(jax.random.key(0), max_batch),
             jnp.zeros((max_batch,), jnp.int32),
             jnp.asarray(session.lengths, jnp.int32),
             jnp.ones((max_batch,), bool), jnp.zeros((max_batch,), bool),
             jnp.full((max_batch,), -1, jnp.int32),
             jnp.zeros((max_batch,), jnp.float32), jnp.ones((max_batch,), bool))

    def blk(cache, tok, keys, counts, lengths, active, done, eos, temp, greedy):
        toks, cache, tok, lengths, done = fused(
            lm.params, cache, tok, keys, counts, lengths, active, done, eos,
            temp, greedy)[:5]  # a model with experts returns its routing sums last
        return toks, cache, tok, keys, counts, lengths, active, done, eos, temp, greedy

    st = blk(*state)
    int(np.asarray(st[0])[0, 0])  # warm + sync
    st = st[1:]
    best = float("inf")
    calls, windows = 2, 3
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(calls):
            toks, *st = blk(*st)
        int(np.asarray(toks)[-1, 0])
        best = min(best, (time.perf_counter() - t0) / calls)
    out["serve_fused_round_device_ms"] = round(best * 1e3, 2)
    out["serve_fused_ms_per_token"] = round(best * 1e3 / fused_steps, 3)
    out["serve_fused_steps"] = fused_steps

    # same-depth/batch fused-16 generate decode for the amortization ratio
    _, cache = lm._prefill[prompt_len](lm.params, jnp.asarray(prompts))
    gen_tok = _fused_decode_window(lm, cache, fused_steps=fused_steps)
    out["serve_generate_fused16_ms_per_token"] = round(gen_tok * 1e3, 3)
    out["serve_fused_vs_generate_fused16"] = round(
        (best / fused_steps) / gen_tok, 3)

    # end-to-end arrival-trace throughput (tentpole headline)
    trace = synthetic_trace(12, 32000, prompt_lens=(prompt_len,),
                            max_new_tokens=48, mean_interarrival_blocks=0.5,
                            seed=0)
    # warm every insert width the staggered arrivals can produce plus the
    # fused block program — compiles must not land in the timed window
    for rows in range(1, max_batch + 1):
        lm._insert_programs(rows, prompt_len)
    warm_eng = ServeEngine(lm, block_steps=fused_steps)
    for item in trace[:max_batch]:
        warm_eng.submit(item["prompt"], 2)
    warm_eng.run()
    eng = ServeEngine(lm, block_steps=fused_steps)
    rep = run_trace(eng, trace)
    out["serve_tokens_per_sec_cb"] = rep["tokens_per_sec"]
    out["serve_cb_requests"] = rep["requests_completed"]
    out["serve_cb_host_ops_per_block"] = rep["host_ops_per_block"]
    out["serve_cb_basis"] = (
        "12-request exponential arrival trace, 128-tok prompts, 48 new "
        "tokens each, 4 slots, fused K=16; warmed wall clock incl. inserts")

    # --- tracing overhead (ISSUE 6 headline): the SAME warmed arrival
    # trace served with structured tracing ON vs OFF, driving engine.run()
    # directly (run_trace would turn tracing on for its latency surface).
    # The tentpole's cost contract — disabled-by-default zero-cost, and
    # enabled tracing rides the host gaps between device blocks — requires
    # traced/untraced >= 0.97; best-of-2 per mode to shed warmup noise.
    def _tps(trace_on: bool) -> float:
        eng_t = ServeEngine(lm, block_steps=fused_steps, trace=trace_on)
        for item in trace:
            eng_t.submit(item["prompt"], item["max_new_tokens"],
                         arrival_block=item["arrival_block"])
        t0 = time.perf_counter()
        comps = eng_t.run()
        dt = time.perf_counter() - t0
        return sum(len(c.tokens) for c in comps) / dt

    tps_off = max(_tps(False) for _ in range(2))
    tps_on = max(_tps(True) for _ in range(2))
    out["serve_tokens_per_sec_untraced"] = round(tps_off, 1)
    out["serve_tokens_per_sec_traced"] = round(tps_on, 1)
    out["serve_tracing_overhead_ratio"] = round(tps_on / tps_off, 3)
    out["serve_tracing_overhead_basis"] = (
        "same 12-request warmed trace as serve_tokens_per_sec_cb, "
        "engine.run() wall clock, best of 2 per mode; ratio = traced tok/s "
        "over untraced tok/s (>= 0.97 required)")

    # --- paged KV + shared-prefix reuse (ISSUE 3 tentpole evidence): the
    # same weights behind a paged CausalLM. Three claims, measured:
    # (a) prefix-hit TTFT (insert a prompt whose long prefix is cached ->
    #     only the suffix prefills) vs cold TTFT, min-over-trials with a
    #     FRESH prompt per cold trial so no trial accidentally hits;
    # (b) HBM: pool bytes vs the slab at the same dims (sizing formula);
    # (c) end-to-end paged engine throughput on a shared-prefix trace.
    try:
        page_size = 16
        ppseq = (prompt_len + 256) // page_size
        lm_p = CausalLM(lcfg, model.params, LlamaForCausalLM,
                        buckets=(64, prompt_len), max_batch=max_batch,
                        page_size=page_size,
                        page_pool_pages=max_batch * ppseq // 2 + max_batch)
        lm_p.compile()
        kv = lm_p.kv_cache_bytes()
        out["paged_hbm_bytes"] = kv["kv_bytes"]
        out["paged_hbm_bytes_vs_slab"] = round(
            kv["kv_bytes"] / kv["kv_slab_bytes"], 3)
        out["serve_paged_page_size"] = page_size
        psess = lm_p.start_session()
        rs_p = np.random.RandomState(7)
        shared = rs_p.randint(1, 32000, (prompt_len - page_size,)).astype(np.int32)

        def paged_ttft(prompt):
            t0 = time.perf_counter()
            lg = lm_p.insert(psess, [0], prompt[None], reserve_tokens=64)
            int(jnp.argmax(lg[0]))            # first token fetch = sync
            dt = time.perf_counter() - t0
            lm_p.retire(psess, [0])
            return dt

        # warm both insert programs (cold: full prompt_len bucket; hit: the
        # 64-token suffix bucket) outside the timed trials
        paged_ttft(rs_p.randint(1, 32000, (prompt_len,)).astype(np.int32))
        warm_hit = np.concatenate([shared, rs_p.randint(
            1, 32000, (page_size,)).astype(np.int32)])
        paged_ttft(warm_hit)
        cold_ts, hit_ts = [], []
        for _ in range(6):
            cold_ts.append(paged_ttft(
                rs_p.randint(1, 32000, (prompt_len,)).astype(np.int32)))
            hit_ts.append(paged_ttft(np.concatenate([
                shared, rs_p.randint(1, 32000, (page_size,)).astype(np.int32)])))
        out["serve_cold_ttft_ms"] = round(float(np.min(cold_ts)) * 1e3, 2)
        out["serve_prefix_hit_ttft_ms"] = round(float(np.min(hit_ts)) * 1e3, 2)
        out["serve_prefix_hit_ttft_ratio"] = round(
            float(np.min(hit_ts)) / float(np.min(cold_ts)), 3)
        out["serve_prefix_hit_tokens"] = psess.paged.stats["prefix_hit_tokens"]
        out["serve_prefix_ttft_basis"] = (
            f"1-slot insert + first-token fetch, min of 6 trials; hit "
            f"prompts share a cached {prompt_len - page_size}-token prefix "
            f"(suffix prefill = {page_size} tokens in a 64-bucket), cold "
            f"prompts are fresh per trial")

        # end-to-end paged engine throughput on the shared-prefix trace.
        # Warm EVERY insert program the trace can hit — any admission-group
        # width x either suffix bucket (cold prompts prefill the full 128
        # bucket, prefix hits the 64 one) — plus the fused block, so no XLA
        # compile lands inside the timed window
        ptrace = synthetic_trace(
            12, 32000, prompt_lens=(page_size,), max_new_tokens=48,
            mean_interarrival_blocks=0.5,
            shared_prefix_len=prompt_len - page_size, seed=0)
        for rows in range(1, max_batch + 1):
            for b in (64, prompt_len):
                lm_p._paged_insert_programs(rows, b)
        warm_p = ServeEngine(lm_p, block_steps=fused_steps)
        for item in ptrace[:max_batch]:
            warm_p.submit(item["prompt"], 2)
        warm_p.run()
        eng_p = ServeEngine(lm_p, block_steps=fused_steps)
        rep_p = run_trace(eng_p, ptrace)
        out["serve_tokens_per_sec_paged"] = rep_p["tokens_per_sec"]
        out["serve_paged_prefix_hit_tokens_trace"] = rep_p["prefix_hit_tokens"]
        out["serve_paged_host_ops_per_block"] = rep_p["host_ops_per_block"]
        del lm_p, psess, warm_p, eng_p
    except Exception as e:  # noqa: BLE001 — paged section additive, never fatal
        out["serve_paged_error"] = f"{type(e).__name__}: {e}"[:120]

    # --- host-memory KV tier (ISSUE 8 tentpole evidence). Two claims:
    # (a) restore beats recompute — TTFT of a prefix hit whose pages sit in
    #     the HOST TIER (admission restores them, checksum-verified) vs the
    #     cold full-prefill TTFT on the same engine;
    # (b) spill beats shed — two shared-prefix tenant families alternate on
    #     a pool too small to keep both prefixes resident, behind a bounded
    #     queue at ~2x pool pressure. Untiered, the loser family's prefix is
    #     DROPPED and its next burst full-prefills (whole-prompt footprint
    #     per request -> pool-bound sheds); tiered, the prefix restores and
    #     stays SHARED (one copy + O(suffix) per request), so the shed rate
    #     falls. Restore-latency p99 is the price tag, reported next to it.
    try:
        page_size = 16
        ppseq = (prompt_len + 256) // page_size
        pool_t = max_batch * ppseq // 4 + max_batch
        lm_t = CausalLM(lcfg, model.params, LlamaForCausalLM,
                        buckets=(64, prompt_len), max_batch=max_batch,
                        page_size=page_size, page_pool_pages=pool_t)
        lm_t.compile()
        rs_t = np.random.RandomState(11)
        shared_t = rs_t.randint(
            1, 32000, (prompt_len - page_size,)).astype(np.int32)

        eng_t = ServeEngine(lm_t, block_steps=fused_steps,
                            host_tier_pages=2 * pool_t)
        pkv_t = eng_t.session.paged
        sess_t = eng_t.session

        def tier_ttft(prompt):
            t0 = time.perf_counter()
            lg = lm_t.insert(sess_t, [0], prompt[None], reserve_tokens=64)
            int(jnp.argmax(lg[0]))          # first-token fetch = sync
            dt = time.perf_counter() - t0
            lm_t.retire(sess_t, [0])
            return dt

        def hit_prompt():
            return np.concatenate([shared_t, rs_t.randint(
                1, 32000, (page_size,)).astype(np.int32)])

        # warm both insert programs (full-bucket cold, suffix-bucket hit)
        # and register the prefix OUTSIDE the timed trials
        tier_ttft(hit_prompt())
        tier_ttft(hit_prompt())
        cold_ts, tiered_ts = [], []
        for _ in range(6):
            # cold re-prefill of the SAME shape: drop the cache (trie AND
            # tier), so the admission prefills the whole prompt from scratch
            pkv_t.prefix.drop_tiered()
            pkv_t.prefix.evict(10 ** 9)
            cold_ts.append(tier_ttft(hit_prompt()))
            # tiered hit: prefix resident in the HOST tier only — the
            # admission restores it, then prefills the suffix
            pkv_t.prefix.spill(10 ** 9)
            tiered_ts.append(tier_ttft(hit_prompt()))
        out["serve_prefix_hit_ttft_ms_tiered"] = round(
            float(np.min(tiered_ts)) * 1e3, 2)
        out["serve_cold_ttft_ms_tierbench"] = round(
            float(np.min(cold_ts)) * 1e3, 2)
        out["serve_tier_restored_pages"] = pkv_t.stats["tier_restored_pages"]
        if pkv_t._restore_ms:
            out["tier_restore_ms_p99"] = round(
                float(np.percentile(pkv_t._restore_ms, 99)), 3)
        out["serve_tier_ttft_basis"] = (
            f"1-slot insert + first-token fetch, min of 6 trials each; "
            f"tiered = the cached {prompt_len - page_size}-token prefix "
            f"sits in the HOST tier (admission restores "
            f"{(prompt_len - 1) // page_size} pages, prefills the "
            f"{page_size}-token suffix); cold = same prompt shape with the "
            f"cache dropped (full-prompt re-prefill); both warmed")
        del eng_t, sess_t, pkv_t

        # (b) shed rate under ~2x pool pressure, untiered vs tiered. Two
        # prefix families BURST alternately on a pool sized so the live
        # hit-footprint fills it exactly — each burst's pressure pushes the
        # idle family's prefix out of the device pool. The engine serves
        # CHUNKED (prefill_chunk_tokens = page_size), so the virtual-time
        # cost of meeting a burst cold is ceil(prompt/C) prefill rounds per
        # stream, while a tiered burst RESTORES the prefix and pays one
        # suffix round — the service-rate gap is what the bounded queue
        # converts into sheds (Mooncake's TTFT-collapse story, measured as
        # shed rate on the deterministic block clock).
        mnt_t = 8
        shared_pages_t = (prompt_len - 1) // page_size
        hit_owned_t = (-(-(prompt_len + mnt_t + fused_steps) // page_size)
                       - shared_pages_t)
        pool_p = max_batch + shared_pages_t + max_batch * hit_owned_t
        lm_p2 = CausalLM(lcfg, model.params, LlamaForCausalLM,
                         buckets=(64, prompt_len), max_batch=max_batch,
                         page_size=page_size, page_pool_pages=pool_p)
        lm_p2.compile()

        def family_burst(seed, start_block):
            tr = synthetic_trace(
                8, 32000, prompt_lens=(page_size,), max_new_tokens=mnt_t,
                mean_interarrival_blocks=0.5,
                shared_prefix_len=prompt_len - page_size, seed=seed)
            for item in tr:
                item["arrival_block"] += start_block
            return tr

        def pressure_trace():
            bursts = [family_burst(5, 0), family_burst(6, 8),
                      family_burst(5, 16), family_burst(6, 24)]
            return sorted(sum(bursts, []),
                          key=lambda d: d["arrival_block"])

        for rows in range(1, max_batch + 1):
            for b in (64, prompt_len):
                lm_p2._paged_insert_programs(rows, b)
        chunk_t = prompt_len // 2
        shed = {}
        for tier_pages in (0, 2 * pool_p):
            warm_t = ServeEngine(lm_p2, block_steps=fused_steps)
            for item in pressure_trace()[:max_batch]:
                warm_t.submit(item["prompt"], 2)
            warm_t.run()
            eng_s = ServeEngine(lm_p2, block_steps=fused_steps,
                                max_queue=1,
                                prefill_chunk_tokens=chunk_t,
                                host_tier_pages=tier_pages)
            rep = run_trace(eng_s, pressure_trace())
            shed[tier_pages] = rep["rejected"] / len(pressure_trace())
            if tier_pages:
                out["serve_tier_spilled_pages_trace"] = \
                    rep.get("tier_spilled_pages")
                out["serve_tier_restored_pages_trace"] = \
                    rep.get("tier_restored_pages")
            del warm_t, eng_s
        out["serve_shed_rate_poolpressure"] = round(shed[0], 4)
        out["serve_shed_rate_poolpressure_tiered"] = round(
            shed[2 * pool_p], 4)
        out["serve_tier_shed_basis"] = (
            f"two {prompt_len - page_size}-token shared-prefix families, 4 "
            f"alternating bursts of 8 reqs @ 0.5 blocks (8-block period), "
            f"{mnt_t} new tokens, pool {pool_p} pages (= scratch + shared "
            f"prefix + live hit footprint) x {max_batch} slots, chunked "
            f"prefill C={chunk_t}, max_queue=1; shed rate = rejected / "
            f"submitted; cold re-prefill costs ceil(prompt/C) rounds where "
            f"a tier restore costs one suffix round; tiered = host tier "
            f"of {2 * pool_p} pages, same trace")
        del lm_t, lm_p2
    except Exception as e:  # noqa: BLE001 — tier section additive, never fatal
        out["serve_tier_error"] = f"{type(e).__name__}: {e}"[:120]

    # --- chunked prefill: decode stall under a long-prompt insert (ISSUE 4
    # tentpole evidence). A heavy-tailed trace (every 4th prompt is a
    # 256-token LONG prompt amid 64-token traffic) drives the same engine
    # twice: unchunked — each long one-shot insert stalls every live token
    # stream for the whole prefill — vs chunked at 128 tokens/round.
    # Reported: inter-token-latency percentiles under load (chunked run)
    # and the worst decode stall a SHORT request suffers (max inter-token
    # wall gap), both modes; the chunked stall must drop toward the
    # no-insert per-block time.
    try:
        long_len = 2 * prompt_len
        lm_i = CausalLM(lcfg, model.params, LlamaForCausalLM,
                        buckets=(64, prompt_len, long_len),
                        max_batch=max_batch)
        lm_i.compile()
        itrace = synthetic_trace(
            10, 32000, prompt_lens=(64,), max_new_tokens=48,
            mean_interarrival_blocks=0.5,
            long_prompt_frac=0.25, long_prompt_len=long_len, seed=2)
        chunk = prompt_len
        reports = {}
        for chunked in (0, chunk):
            # warm every program either schedule can hit outside the timed
            # window: insert widths per bucket, the fused block, and (for
            # the chunked run) the 1-row chunk-extend at chunk width
            for rows in range(1, max_batch + 1):
                for b in (64, prompt_len, long_len):
                    lm_i._insert_programs(rows, b)
            if chunked:
                lm_i._chunk_extend_programs(1, chunk)
            warm = ServeEngine(lm_i, block_steps=fused_steps,
                               prefill_chunk_tokens=chunked)
            for item in itrace[:max_batch]:
                warm.submit(item["prompt"][:64], 2)
            warm.run()
            eng_i = ServeEngine(lm_i, block_steps=fused_steps,
                                prefill_chunk_tokens=chunked)
            reports[chunked] = run_trace(eng_i, itrace)

        def short_stall(rep):
            gaps = [r["max_itl_gap_ms"] for r in rep["per_request"]
                    if r["prompt_len"] < long_len]
            return round(max(gaps), 2) if gaps else None

        out["serve_itl_p50_ms"] = reports[chunk]["itl_p50_ms"]
        out["serve_itl_p99_ms"] = reports[chunk]["itl_p99_ms"]
        out["serve_itl_p99_ms_unchunked"] = reports[0]["itl_p99_ms"]
        out["serve_decode_stall_ms_longprompt"] = short_stall(reports[0])
        out["serve_decode_stall_ms_longprompt_chunked"] = short_stall(
            reports[chunk])
        out["serve_prefill_chunk_tokens"] = chunk
        out["serve_chunk_program_calls"] = reports[chunk]["chunk_program_calls"]
        out["serve_itl_basis"] = (
            f"10-request trace, 64-tok prompts with every 4th a "
            f"{long_len}-tok long prompt, 48 new tokens each, "
            f"{max_batch} slots, fused K={fused_steps}; stall = max "
            f"inter-token wall gap over SHORT requests; chunked = "
            f"{chunk}-tok prefill chunks, warmed both runs")
        del lm_i, warm, eng_i
    except Exception as e:  # noqa: BLE001 — chunked section additive, never fatal
        out["serve_chunked_error"] = f"{type(e).__name__}: {e}"[:120]

    # --- prefill/decode disaggregation (ISSUE 11 tentpole evidence): the
    # SAME heavy-tailed interference trace as the chunked section, served
    # by 1 dedicated prefill worker handing checksummed KV-page handoffs
    # to 1 dedicated decode worker. Chunked prefill BOUNDS the decode
    # stall; disaggregation removes it — no prompt ever appears in the
    # decode worker's block. Reported on the PER-WORKER decode clock (the
    # decode worker's own dispatch/fetch/adoption wall per block — what a
    # dedicated decode host delivers; this harness interleaves both
    # workers in one thread, so raw wall gaps would double-charge the
    # prefill time a real deployment runs elsewhere; the in-process wall
    # number rides the sidecar for the caveat trail).
    try:
        from neuronx_distributed_tpu.inference.disagg import (
            DisaggRouter, run_disagg_trace,
        )
        long_len = 2 * prompt_len
        page_size = 16
        ppseq = (prompt_len + 256) // page_size
        lm_d = CausalLM(lcfg, model.params, LlamaForCausalLM,
                        buckets=(64, prompt_len, long_len),
                        max_batch=max_batch, page_size=page_size,
                        page_pool_pages=max_batch * ppseq + max_batch)
        lm_d.compile()
        dtrace = synthetic_trace(
            10, 32000, prompt_lens=(64,), max_new_tokens=48,
            mean_interarrival_blocks=0.5,
            long_prompt_frac=0.25, long_prompt_len=long_len, seed=2)
        # warm every program either worker can hit (paged insert widths per
        # bucket + the fused block) outside the measured run
        for rows in range(1, max_batch + 1):
            for b in (64, prompt_len, long_len):
                lm_d._paged_insert_programs(rows, b)
        warm_d = ServeEngine(lm_d, block_steps=fused_steps)
        for item in dtrace[:max_batch]:
            warm_d.submit(item["prompt"][:64], 2)
        warm_d.run()
        # ... and the migration path itself (adoption-side page writes +
        # cache_index install compile on first use): one warm handoff run
        warm_rd = DisaggRouter(lm_d, 2, prefill_replicas=1,
                               block_steps=fused_steps,
                               rng=jax.random.key(1))
        for item in dtrace[:2]:
            warm_rd.submit(item["prompt"][:64], 2)
        warm_rd.run(max_blocks=200)
        del warm_rd
        r_d = DisaggRouter(lm_d, 2, prefill_replicas=1,
                           block_steps=fused_steps,
                           prefill_chunk_tokens=prompt_len,
                           rng=jax.random.key(0))
        drep = run_disagg_trace(r_d, dtrace)
        out["serve_itl_p50_ms_disagg"] = drep["itl_p50_ms_decode_clock"]
        out["serve_itl_p99_ms_disagg"] = drep["itl_p99_ms_decode_clock"]
        out["serve_decode_stall_ms_longprompt_disagg"] = \
            drep["decode_stall_excess_ms"]
        out["serve_itl_p99_ms_disagg_inproc"] = drep["itl_p99_ms"]
        out["serve_handoff_gap_ms_p99"] = drep["handoff_gap_ms_p99"]
        out["serve_disagg_handoffs"] = drep["handoffs_adopted"]
        out["serve_disagg_handoff_pages"] = drep["handoff_pages"]
        out["serve_disagg_basis"] = (
            f"same 10-request heavy-tailed trace as serve_itl_p99_ms "
            f"(64-tok prompts, every 4th {long_len}-tok), 48 new tokens, "
            f"1 prefill + 1 decode worker x {max_batch} slots, fused "
            f"K={fused_steps}, page {page_size}, chunked C={prompt_len} "
            f"WITHIN the prefill worker; latencies on the decode worker's "
            f"own per-block clock (dispatch+fetch+adoption wall — the "
            f"dedicated-host basis; in-process wall in "
            f"serve_itl_p99_ms_disagg_inproc); stall = worst short-request "
            f"gap minus the run's median gap")
        del lm_d, warm_d, r_d
    except Exception as e:  # noqa: BLE001 — disagg section additive, never fatal
        out["serve_disagg_error"] = f"{type(e).__name__}: {e}"[:120]

    # --- overload + crash recovery (ISSUE 5 tentpole evidence). Deadlines
    # live on the virtual block clock (block_time_ms=1.0 -> ms == blocks),
    # so miss rates are DETERMINISTIC; goodput (in-deadline tokens per wall
    # second) is the wall-clock half. Capacity here: max_batch slots x
    # ceil(32/K)=2 blocks/request -> ~2 requests/block; the 2x trace offers
    # ~4/block, so the unbounded queue's wait grows ~1 block per block and
    # most late arrivals blow the 4-block completion deadline — while the
    # bounded queue sheds the overflow EARLY (Rejected + retry_after) and
    # keeps every admitted request on time.
    try:
        mnt = 32
        deadline_blocks = 4.0       # 2 service blocks + 2 of slack

        def overload_trace(inter, n):
            return synthetic_trace(
                n, 32000, prompt_lens=(prompt_len,), max_new_tokens=mnt,
                mean_interarrival_blocks=inter, deadline_ms=deadline_blocks,
                seed=3)

        for rows in range(1, max_batch + 1):
            lm._insert_programs(rows, prompt_len)

        def run_overload(trace, max_queue):
            warm = ServeEngine(lm, block_steps=fused_steps)
            for item in trace[:max_batch]:
                warm.submit(item["prompt"], 2)
            warm.run()
            eng = ServeEngine(lm, block_steps=fused_steps,
                              max_queue=max_queue, shed_policy="deadline")
            return run_trace(eng, trace)

        r1 = run_overload(overload_trace(0.6, 16), max_queue=max_batch)
        r2_shed = run_overload(overload_trace(0.25, 32), max_queue=max_batch)
        r2_noshed = run_overload(overload_trace(0.25, 32), max_queue=None)
        out["serve_goodput_1x"] = r1["goodput_tokens_per_sec"]
        out["serve_goodput_2x_overload"] = r2_shed["goodput_tokens_per_sec"]
        if r1["goodput_tokens_per_sec"]:
            out["serve_goodput_2x_vs_1x"] = round(
                r2_shed["goodput_tokens_per_sec"]
                / r1["goodput_tokens_per_sec"], 3)
        out["serve_deadline_miss_rate_shed"] = r2_shed["deadline_miss_rate"]
        out["serve_deadline_miss_rate_noshed"] = r2_noshed["deadline_miss_rate"]
        out["serve_overload_rejected_2x"] = r2_shed["rejected"]
        out["serve_overload_expired_2x_noshed"] = r2_noshed["expired"]
        out["serve_overload_basis"] = (
            f"{prompt_len}-tok prompts, {mnt} new tokens, {max_batch} slots, "
            f"fused K={fused_steps}; deadline {deadline_blocks:g} blocks on "
            f"the virtual clock (block_time_ms=1); 1x = 16 reqs @ 0.6 "
            f"blocks interarrival, 2x = 32 reqs @ 0.25; shed = "
            f"max_queue={max_batch}, policy=deadline; miss rate counts "
            f"rejected + expired + late over all submissions")

        # crash-recovery replay cost: snapshot a mid-trace engine with a
        # full slot pool, restore into a fresh engine (the restore replays
        # every in-flight request's prompt+generated through prefill and
        # resumes bit-identical) — the wall cost of coming back from a kill
        eng_r = ServeEngine(lm, block_steps=fused_steps)
        for item in overload_trace(0.0, max_batch):
            eng_r.submit(item["prompt"], mnt)
        eng_r.step_block()
        eng_r.step_block()
        snap = eng_r.snapshot()
        t0 = time.perf_counter()
        eng_restored = ServeEngine.from_snapshot(lm, snap)
        out["serve_recovery_replay_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 2)
        out["serve_recovery_restored_requests"] = \
            eng_restored.stats["restored_requests"]
        del eng_r, eng_restored
    except Exception as e:  # noqa: BLE001 — overload section additive, never fatal
        out["serve_overload_error"] = f"{type(e).__name__}: {e}"[:120]

    # --- multi-replica front door (ISSUE 7 tentpole evidence): N=4 paged
    # replicas (one shared lm, four sessions) behind the Router. Measured:
    # (a) aggregate goodput at ~2x overload with a bursting tenant + a
    #     compliant tenant, prefix-affinity placement vs the round-robin
    #     baseline — each tenant's traffic shares its OWN hot prefix, so
    #     affinity concentrates radix reuse (O(suffix) prefills) where
    #     round-robin smears cold full-bucket prefills across the fleet;
    # (b) the fairness ratio: the compliant tenant's p99 ITL in the mixed
    #     run over its SOLO run — WFQ must hold it <= ~1.2x;
    # (c) the failover replay block cost and the graceful-drain wall time
    #     on an N=2 fleet.
    try:
        from neuronx_distributed_tpu.inference.router import (
            Router, run_router_trace,
        )
        page_size = 16
        ppseq = (prompt_len + 256) // page_size
        lm_r = CausalLM(lcfg, model.params, LlamaForCausalLM,
                        buckets=(64, prompt_len), max_batch=max_batch,
                        page_size=page_size,
                        page_pool_pages=max_batch * ppseq // 2 + max_batch)
        lm_r.compile()
        mnt_r = 24

        def tenant_trace(n, inter, tenant, seed, deadline=None):
            tr = synthetic_trace(
                n, 32000, prompt_lens=(page_size,), max_new_tokens=mnt_r,
                mean_interarrival_blocks=inter,
                shared_prefix_len=prompt_len - page_size,
                deadline_ms=deadline, seed=seed)
            for item in tr:
                item["tenant"] = tenant
            return tr

        # warm every program the traces can hit (cold full-bucket insert,
        # prefix-hit suffix bucket, fused block) outside the timed windows
        for rows in range(1, max_batch + 1):
            for b in (64, prompt_len):
                lm_r._paged_insert_programs(rows, b)
        warm_r = ServeEngine(lm_r, block_steps=fused_steps)
        for item in tenant_trace(max_batch, 0.0, "w", 3):
            warm_r.submit(item["prompt"], 2)
        warm_r.run()

        deadline_r = 10.0
        compliant = tenant_trace(8, 0.4, "compliant", 21,
                                 deadline=deadline_r)
        burst = tenant_trace(40, 0.08, "burst", 23, deadline=deadline_r)
        mixed = sorted(compliant + burst,
                       key=lambda d: d["arrival_block"])

        def run_router(placement, trace):
            r = Router(lm_r, 4, placement=placement,
                       block_steps=fused_steps, rng=jax.random.key(0))
            rep = run_router_trace(r, trace)
            del r
            return rep

        solo = run_router("affinity", compliant)
        mix = run_router("affinity", mixed)
        rr_rep = run_router("round_robin", mixed)
        out["serve_agg_goodput_2x_n4"] = mix["goodput_tokens_per_sec"]
        out["serve_agg_goodput_2x_n4_rr"] = rr_rep["goodput_tokens_per_sec"]
        out["serve_router_affinity_placements"] = mix["affinity_placements"]
        solo_p99 = solo["per_tenant"]["compliant"]["itl_p99_ms"]
        mix_p99 = mix["per_tenant"]["compliant"]["itl_p99_ms"]
        if solo_p99 and mix_p99:
            out["serve_tenant_p99_fairness_ratio"] = round(
                mix_p99 / solo_p99, 3)
        out["serve_router_basis"] = (
            f"N=4 paged replicas x {max_batch} slots, K={fused_steps}, "
            f"page {page_size}; per-tenant {prompt_len - page_size}-token "
            f"shared prefixes; compliant 8 reqs @ 0.4 blocks interarrival "
            f"vs burst 40 @ 0.08, {mnt_r} new tokens, deadline "
            f"{deadline_r:g} blocks (block_time_ms=1); fairness ratio = "
            f"compliant p99 ITL mixed/solo; goodput vs round_robin "
            f"placement on the identical trace")

        # failover replay cost: crash replica 0 mid-decode on N=2; the
        # reported block is the one where the router detects the silence,
        # re-places the lost streams, and the survivor replays them
        r_f = Router(lm_r, 2, block_steps=fused_steps,
                     rng=jax.random.key(0), crash_at=[(2, 0)])
        for item in tenant_trace(2 * max_batch, 0.1, "t", 29):
            r_f.submit(item["prompt"], item["max_new_tokens"], tenant="t",
                       arrival_block=item["arrival_block"])
        fail_ms = None
        seen = 0
        while True:
            t0 = time.perf_counter()
            more = r_f.step_block()
            dt = (time.perf_counter() - t0) * 1e3
            if r_f.stats["failovers"] > seen:
                seen = r_f.stats["failovers"]
                fail_ms = dt
            if not more:
                break
        out["serve_failover_replay_ms"] = (round(fail_ms, 2)
                                           if fail_ms else None)
        out["serve_failover_requests"] = r_f.stats["failed_over_requests"]

        # graceful-drain wall cost: under load, drain one of two replicas —
        # queued work migrates, decoding streams finish, then snapshot
        r_d = Router(lm_r, 2, block_steps=fused_steps,
                     rng=jax.random.key(0))
        for item in tenant_trace(2 * max_batch, 0.1, "t", 31):
            r_d.submit(item["prompt"], item["max_new_tokens"], tenant="t",
                       arrival_block=item["arrival_block"])
        r_d.step_block()
        r_d.drain(0)
        r_d.run()
        out["serve_drain_ms"] = r_d.last_drain_ms
        out["serve_drain_migrated_requests"] = \
            r_d.stats["drain_migrated_requests"]
        out["serve_failover_drain_basis"] = (
            f"N=2 paged replicas, {2 * max_batch} reqs @ 0.1 blocks, "
            f"{mnt_r} new tokens; failover = wall ms of the router block "
            f"covering heartbeat-miss detection + re-placement + survivor "
            f"replay prefills; drain = drain() call to replica park "
            f"(migration + remaining decode + snapshot)")
        del lm_r, warm_r, r_f, r_d
    except Exception as e:  # noqa: BLE001 — router section additive, never fatal
        out["serve_router_error"] = f"{type(e).__name__}: {e}"[:120]

    # --- SLO-driven autoscaling (ISSUE 12 tentpole evidence): the SAME
    # diurnal trace (streamed — synthetic_trace_stream, no materialized
    # request list) served by a FIXED max-provisioned N=4 fleet vs an
    # elastic fleet starting at 1 replica under the Autoscaler policy
    # (scale-up on weighted backlog, scale-down drains + parks, warm
    # unparks from the parked snapshot). Streams are bit-identical by the
    # per-request rng contract, so the headline is capacity honesty:
    # goodput PER PROVISIONED REPLICA-BLOCK, autoscaled over fixed — >= 1.0
    # means elasticity tracked the diurnal load without giving back
    # deadline goodput. Both runs live on the virtual block clock, so the
    # ratio is deterministic (no wall noise); the wall numbers (spawn cost)
    # ride the sidecar.
    try:
        from neuronx_distributed_tpu.inference.autoscale import (
            Autoscaler, AutoscalePolicy,
        )
        from neuronx_distributed_tpu.inference.engine import (
            synthetic_trace_stream,
        )
        from neuronx_distributed_tpu.inference.router import (
            Router as _ARouter, run_router_trace as _arun,
        )
        page_size = 16
        ppseq = (prompt_len + 256) // page_size
        lm_as = CausalLM(lcfg, model.params, LlamaForCausalLM,
                         buckets=(prompt_len,), max_batch=max_batch,
                         page_size=page_size,
                         page_pool_pages=max_batch * ppseq + max_batch)
        lm_as.compile()
        mnt_a = 24
        deadline_a = 16.0

        def diurnal_stream():
            return synthetic_trace_stream(
                48, 32000, prompt_lens=(prompt_len,), max_new_tokens=mnt_a,
                mean_interarrival_blocks=0.5, deadline_ms=deadline_a,
                diurnal=0.85, diurnal_period_blocks=32, seed=11)

        for rows in range(1, max_batch + 1):
            lm_as._paged_insert_programs(rows, prompt_len)
        warm_a = ServeEngine(lm_as, block_steps=fused_steps)
        for item in list(diurnal_stream())[:max_batch]:
            warm_a.submit(item["prompt"], 2)
        warm_a.run()

        def ontime_tokens(r):
            return sum(len(c.tokens) for c in r.completed
                       if not (c.deadline_missed or c.expired or c.cancelled))

        r_fix = _ARouter(lm_as, 4, block_steps=fused_steps,
                         rng=jax.random.key(0))
        _arun(r_fix, diurnal_stream())
        pol_a = AutoscalePolicy(
            min_replicas=1, max_replicas=4, backlog_high_blocks=1.0,
            up_patience_blocks=2, down_utilization=0.4,
            down_patience_blocks=6, cooldown_blocks=6)
        r_auto = _ARouter(lm_as, 1, block_steps=fused_steps,
                          rng=jax.random.key(0), autoscaler=Autoscaler(pol_a))
        rep_auto = _arun(r_auto, diurnal_stream())
        fix_g = ontime_tokens(r_fix) / max(r_fix.stats["replica_blocks"], 1)
        auto_g = ontime_tokens(r_auto) / max(r_auto.stats["replica_blocks"], 1)
        out["serve_goodput_autoscale_vs_fixed"] = round(auto_g / fix_g, 3)
        a_sec = rep_auto["autoscale"]
        out["serve_scaleup_time_to_ready_blocks"] = \
            a_sec["time_to_ready_blocks_mean"]
        out["serve_autoscale_scale_ups"] = a_sec["scale_ups"]
        out["serve_autoscale_scale_downs"] = a_sec["scale_downs"]
        out["serve_autoscale_warm_spawns"] = a_sec["warm_spawns"]
        out["serve_autoscale_replica_blocks"] = r_auto.stats["replica_blocks"]
        out["serve_fixed_replica_blocks"] = r_fix.stats["replica_blocks"]
        out["serve_scaleup_spawn_ms"] = a_sec["last_spawn_ms"]
        out["serve_autoscale_basis"] = (
            f"48-request streamed diurnal trace (amp 0.85, period 32 "
            f"blocks, 0.5 blocks mean interarrival), {prompt_len}-tok "
            f"prompts, {mnt_a} new tokens, deadline {deadline_a:g} blocks; "
            f"elastic 1..4 replicas (backlog>1 block/replica for 2 blocks "
            f"scales up, util<0.4 for 6 blocks drains+parks, cooldown 6) "
            f"vs fixed N=4; ratio = on-deadline tokens per replica-block, "
            f"autoscaled/fixed (virtual clock — deterministic); "
            f"time-to-ready = blocks from scale decision to the new "
            f"replica's first placement")
        del lm_as, warm_a, r_fix, r_auto
    except Exception as e:  # noqa: BLE001 — autoscale section additive, never fatal
        out["serve_autoscale_error"] = f"{type(e).__name__}: {e}"[:120]

    # --- multi-LoRA serving (ISSUE 10 tentpole evidence). Two claims:
    # (a) a mixed 8-adapter Zipf trace served through the pooled low-rank
    #     path (per-row gathered y += s·(x@A)@B, ONE compiled program for
    #     any adapter mix) holds >= 0.9x the throughput of the single-
    #     merged-model baseline on the IDENTICAL trace — the S-LoRA
    #     economics: the rank-r correction is marginal next to the base
    #     matmuls, while the merged baseline can serve exactly ONE tenant's
    #     fine-tune per model copy;
    # (b) adapter-switch cost: wall ms to make a cold adapter device-
    #     resident (pad + checksum + slot write at the pool seam) — the
    #     price of churning past the pool's residency.
    try:
        from neuronx_distributed_tpu.lora import (
            LoraConfig as _LoraCfg, init_lora, merge_lora,
        )

        n_ad, r_ad = 8, 8
        lm_a = CausalLM(lcfg, model.params, LlamaForCausalLM,
                        buckets=(prompt_len,), max_batch=max_batch,
                        lora_rank=r_ad, lora_slots=n_ad + 1)
        lm_a.compile()
        acfg_ml = _LoraCfg(r=r_ad)
        adapters_ml = {}
        for i in range(n_ad):
            ad_i = init_lora(model.params, acfg_ml, jax.random.key(500 + i))
            adapters_ml[f"a{i}"] = {
                k: {"lora_a": v["lora_a"],
                    "lora_b": 0.01 * jax.random.normal(
                        jax.random.fold_in(jax.random.key(600 + i), j),
                        v["lora_b"].shape, jnp.float32)}
                for j, (k, v) in enumerate(sorted(ad_i.items()))}

        ml_trace = synthetic_trace(
            12, 32000, prompt_lens=(prompt_len,), max_new_tokens=48,
            mean_interarrival_blocks=0.5, adapters=n_ad, adapter_skew=1.0,
            seed=0)

        def ml_run(lm_, labeled):
            for rows in range(1, max_batch + 1):
                lm_._insert_programs(rows, prompt_len)
            warm = ServeEngine(lm_, block_steps=fused_steps)
            if labeled:
                for n_, ad_ in adapters_ml.items():
                    warm.register_adapter(n_, ad_, acfg_ml)
            for item in ml_trace[:max_batch]:
                warm.submit(item["prompt"], 2,
                            adapter=item.get("adapter") if labeled else None)
            warm.run()
            eng_ = ServeEngine(lm_, block_steps=fused_steps)
            if labeled:
                for n_, ad_ in adapters_ml.items():
                    eng_.register_adapter(n_, ad_, acfg_ml)
            tr = (ml_trace if labeled
                  else [{k: v for k, v in item.items() if k != "adapter"}
                        for item in ml_trace])
            return eng_, run_trace(eng_, tr)

        eng_a, rep_a = ml_run(lm_a, labeled=True)
        out["serve_tokens_per_sec_multilora"] = rep_a["tokens_per_sec"]
        out["serve_multilora_adapter_loads"] = rep_a["adapter_loads"]
        out["serve_multilora_adapters_resident"] = \
            len(rep_a["adapters_resident"])

        # single-merged baseline: adapter a0 merged into the base weights,
        # no LoRA machinery at serve time — one tenant per model copy
        merged = merge_lora(model.params, adapters_ml["a0"], acfg_ml)
        lm_m = CausalLM(lcfg, merged, LlamaForCausalLM,
                        buckets=(prompt_len,), max_batch=max_batch)
        lm_m.compile()
        _eng_m, rep_m = ml_run(lm_m, labeled=False)
        out["serve_tokens_per_sec_merged_single"] = rep_m["tokens_per_sec"]
        if rep_m["tokens_per_sec"]:
            out["serve_multilora_vs_merged"] = round(
                rep_a["tokens_per_sec"] / rep_m["tokens_per_sec"], 3)

        # adapter-switch overhead at the pool seam: cold load (evict first)
        # vs resident re-pin, min of 6 each
        pool = eng_a.session.adapters
        cold_ts, hit_ts = [], []
        for _ in range(6):
            pool.evict("a0")
            t0 = time.perf_counter()
            pool.acquire("a0")
            cold_ts.append(time.perf_counter() - t0)
            pool.release("a0")
            t0 = time.perf_counter()
            pool.acquire("a0")
            hit_ts.append(time.perf_counter() - t0)
            pool.release("a0")
        out["adapter_switch_overhead_ms"] = round(
            float(np.min(cold_ts)) * 1e3, 3)
        out["adapter_acquire_hit_ms"] = round(
            float(np.min(hit_ts)) * 1e3, 3)
        out["adapter_bytes_per_slot"] = pool.adapter_bytes()
        out["serve_multilora_basis"] = (
            f"{n_ad} rank-{r_ad} adapters (Zipf skew 1.0) over 12 reqs @ "
            f"0.5 blocks, {prompt_len}-token prompts, 48 new tokens, "
            f"pool {n_ad + 1} slots (no churn); baseline = a0 merged into "
            f"the base weights serving the identical unlabeled trace; "
            f"switch overhead = cold acquire (pad + checksum + device "
            f"slot write) vs resident re-pin, min of 6")
        del lm_a, lm_m, eng_a, _eng_m, pool
    except Exception as e:  # noqa: BLE001 — multilora section additive, never fatal
        out["serve_multilora_error"] = f"{type(e).__name__}: {e}"[:120]

    # --- structured decoding (ISSUE 13 tentpole evidence): factored out
    # as bench_structured() so scripts/bench_cpu_basis.py
    # --structured-update can refresh JUST these keys over a committed
    # baseline (ISSUE 15 bench-surface audit: r06/r07 predate PR 13, so
    # the structured headline keys were absent from every committed
    # serving artifact and therefore never gated)
    out.update(bench_structured(lcfg, model.params, prompt_len=prompt_len,
                                max_batch=max_batch,
                                fused_steps=fused_steps))

    # --- paged decode kernel + int8 KV pages (ISSUE 17 tentpole
    # evidence): factored out as bench_paged_kernel() so
    # scripts/bench_cpu_basis.py --kernel-update can refresh just these
    # keys over a committed baseline.
    out.update(bench_paged_kernel(lcfg, model.params, prompt_len=prompt_len,
                                  max_batch=max_batch,
                                  fused_steps=fused_steps))

    # --- async double-buffered block loop (ISSUE 19 tentpole evidence):
    # factored out as bench_async_loop() so scripts/bench_cpu_basis.py
    # --async-update can refresh just these keys over a committed
    # baseline. Runs at its own SMALL fused_steps (4) — the regime where
    # the inter-block host pass dominates and the overlap pays most.
    out.update(bench_async_loop(lcfg, model.params, prompt_len=prompt_len,
                                max_batch=max_batch))

    # --- persistent conversation tier (ISSUE 20 tentpole evidence):
    # factored out as bench_park_resume() so scripts/bench_cpu_basis.py
    # --park-update can refresh just these keys over a committed baseline.
    out.update(bench_park_resume(lcfg, model.params, prompt_len=prompt_len,
                                 max_batch=max_batch))

    # --- TP-sharded serving (ISSUE 16 tentpole evidence): factored out as
    # bench_serving_tp() so scripts/bench_cpu_basis.py --tp-update can
    # refresh just these keys. NOTE: rebuilds its own params per TP world
    # (mesh state is torn down and re-initialized inside the section).
    out.update(bench_serving_tp(lcfg, prompt_len=prompt_len,
                                max_batch=max_batch,
                                fused_steps=fused_steps))


    # --- fleet-scale scheduler soak (ROADMAP #18, ISSUE 14 tentpole):
    # 100 sim replicas x 1k/100k/1M virtual-clock requests through the
    # FULL Router/ServeEngine control plane with a host-only stub model
    # (inference/simlm.py — zero XLA, real page/slot accounting) in
    # streaming mode. The deliverable is the SCALING CURVE: us of host
    # wall per completed request at each scale, which the heap-backed
    # scheduler (inference/schedq.py) must keep flat — the 1M/1k ratio is
    # the sub-linearity gate — plus the RSS leak slope over the final 80%
    # of the 1M run (~0 when every per-request structure is bounded).
    out.update(bench_sched_soak())

    # compile-vs-execute split (ISSUE 6 satellite): first-call XLA compile
    # wall ms per program signature, recorded by CausalLM._time_compile —
    # sidecar-only (a dict of long keys has no place in the headline)
    out["compile_ms_by_program"] = dict(lm.compile_ms)

    del lm, model, session, fused, st, cache
    gc.collect()
    return out



def bench_structured(lcfg, params, prompt_len=128, max_batch=4,
                     fused_steps=16) -> dict:
    """Structured-decoding serving section (ISSUE 13 tentpole evidence),
    factored out of bench_serving (ISSUE 15) so the CPU-basis baseline
    driver can refresh JUST these keys over a committed artifact without
    re-paying the full tiny-dims compile sweep. Three claims:

    * ``serve_structured_parse_rate`` — every constrained completion
      fullmatches its grammar (regex walk / json.loads): MUST be 1.0, by
      construction (budget-aware token-DFA masking inside the scan);
    * ``serve_itl_p50_ms_structured_vs_freeform`` — a mixed 50%
      structured trace holds >= 0.9x the free-form-only ITL on the same
      pool: the per-step mask (two gathers + a where, inside the
      compiled scan) must not stall decode;
    * ``grammar_compile_ms`` — the one-time host cost of regex/schema ->
      token-DFA compilation over the 32k vocab (amortized over every
      request that ever pins the grammar).

    Takes the serving model's ``(lcfg, params)`` — it builds its own
    grammar-tailed and grammarless CausalLM pools, so any dims work
    (bench_serving passes 13B layer dims; bench_cpu_basis tiny dims).
    """
    from neuronx_distributed_tpu.inference import CausalLM, ServeEngine
    from neuronx_distributed_tpu.inference.engine import run_trace, synthetic_trace
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

    out = {}
    try:
        from neuronx_distributed_tpu.inference.grammar import (
            json_schema_to_regex as _js2re,  # noqa: F401 (import check)
        )

        # grammar menu: two run-to-budget shapes (digits, identifier — the
        # budget-aware mask parks them in an accept state at token 48, so
        # their pool occupancy matches the free-form baseline and the
        # ratio isolates MASKING cost, not early-retirement churn) plus
        # one early-terminal JSON object for the accept-freeze path
        gr_specs = {
            "g_int": {"regex": "-?[0-9]{1,64}"},
            "g_word": {"regex": "[a-z][a-z0-9]*"},
            "g_obj": {"json_schema": {"type": "object", "properties": {
                "name": {"type": "string"}, "count": {"type": "integer"},
                "ok": {"type": "boolean"}}}},
        }
        lm_g = CausalLM(lcfg, params, LlamaForCausalLM,
                        buckets=(prompt_len,), max_batch=max_batch,
                        grammar_slots=len(gr_specs) + 1, grammar_states=96)
        lm_g.compile()
        gr_trace = synthetic_trace(
            12, 32000, prompt_lens=(prompt_len,), max_new_tokens=48,
            mean_interarrival_blocks=0.5, grammar_frac=0.5,
            grammars=tuple(gr_specs), seed=0)

        def gr_run(lm_, labeled):
            # warm the WHOLE admission path outside the measured window —
            # cmd_generate's discipline: labeled staggered submissions
            # (pairs -> 1- and 2-row insert widths) compile the masked
            # first-token sampler shapes and the grammar-tailed fused
            # block, so the measured runs time steady-state blocks, not
            # first-call eager compiles (which are process-global, so the
            # run ORDER would otherwise silently favor whichever ran last)
            for rows in range(1, max_batch + 1):
                lm_._insert_programs(rows, prompt_len)
            warm = ServeEngine(lm_, block_steps=fused_steps)
            names = list(gr_specs) if labeled else []
            if labeled:
                for n_, spec in gr_specs.items():
                    warm.register_grammar(n_, **spec)
            for i, item in enumerate(gr_trace[:max_batch]):
                g = names[i % len(names)] if names else None
                warm.submit(item["prompt"], 26 if g else 2,
                            arrival_block=i // 2, grammar=g)
            warm.run()
            eng_ = ServeEngine(lm_, block_steps=fused_steps)
            if labeled:
                for n_, spec in gr_specs.items():
                    eng_.register_grammar(n_, **spec)
            tr = (gr_trace if labeled
                  else [{k: v for k, v in item.items() if k != "grammar"}
                        for item in gr_trace])
            return eng_, run_trace(eng_, tr)

        eng_g, rep_g = gr_run(lm_g, labeled=True)
        gpool = eng_g.session.grammars
        constrained = [c for c in eng_g.completed if c.grammar is not None]
        parsed = sum(1 for c in constrained
                     if gpool.grammar(c.grammar).fullmatch_ids(c.tokens))
        out["serve_structured_parse_rate"] = (
            round(parsed / len(constrained), 3) if constrained else None)
        out["serve_structured_requests"] = len(constrained)
        out["serve_structured_finish_reasons"] = \
            rep_g["structured"]["finish_reasons"]
        out["grammar_compile_ms"] = round(max(
            gpool.compile_ms_of(n) for n in gr_specs), 3)
        out["grammar_bytes_per_slot"] = gpool.grammar_bytes()
        out["serve_itl_p50_ms_structured"] = rep_g["itl_p50_ms"]

        # free-form baseline: the identical trace, labels stripped, on a
        # pool compiled WITHOUT grammar support (the bitwise-identity
        # oracle's reference programs)
        lm_gf = CausalLM(lcfg, params, LlamaForCausalLM,
                         buckets=(prompt_len,), max_batch=max_batch)
        lm_gf.compile()
        _eng_f, rep_f = gr_run(lm_gf, labeled=False)
        out["serve_itl_p50_ms_freeform"] = rep_f["itl_p50_ms"]
        if rep_g["itl_p50_ms"]:
            out["serve_itl_p50_ms_structured_vs_freeform"] = round(
                rep_f["itl_p50_ms"] / rep_g["itl_p50_ms"], 3)
        out["serve_structured_basis"] = (
            f"3 grammars (digit run + identifier — run-to-budget, so "
            f"occupancy matches the baseline and the ratio isolates "
            f"masking cost — plus an early-terminal JSON-schema object) "
            f"over 12 reqs @ 0.5 blocks, 50% constrained, {prompt_len}-"
            f"token prompts, 48 new tokens, pool {len(gr_specs) + 1} "
            f"slots, 96 padded states over the 32k default token table; "
            f"parse rate = DFA fullmatch of every constrained completion "
            f"(json.loads-compatible by construction); ratio = free-form-"
            f"only ITL p50 / mixed-trace ITL p50 on the same dims (>= 0.9 "
            f"gate); compile ms = max one-time host DFA compile")
        del lm_g, lm_gf, eng_g, _eng_f, gpool
    except Exception as e:  # noqa: BLE001 — structured section additive, never fatal
        out["serve_structured_error"] = f"{type(e).__name__}: {e}"[:120]
    return out


def bench_paged_kernel(lcfg, params, prompt_len=128, max_batch=4,
                       fused_steps=16) -> dict:
    """Paged flash-attention kernel + int8 KV pages (ISSUE 17 tentpole
    evidence), a standalone function like :func:`bench_structured` so
    ``scripts/bench_cpu_basis.py --kernel-update`` can refresh JUST these
    keys over a committed artifact. Three claims:

    * ``serve_tokens_per_sec_paged_kernel`` — end-to-end engine
      throughput on the paged section's shared-prefix trace with the
      block-sparse decode kernel in the scan (``paged_attn_kernel=True``:
      decode reads the per-slot block table directly and never
      materializes the (b, max_seq_len) gather). CPU basis runs the
      kernel in Pallas interpret mode, so the absolute number is NOT the
      perf claim there — the key exists so the TPU rounds have a gated
      slot and the CPU rounds prove the path serves traffic end to end;
    * ``paged_hbm_bytes_vs_slab_int8`` — int8 pool bytes (int8 K/V pools
      + fp32 per-page scales) over the UN-quantized slab at the same
      dims: the sizing claim, must stay <= 0.5;
    * ``serve_greedy_match_rate_int8kv`` — token-for-token greedy stream
      agreement of the int8-paged engine against the fp32 gather path on
      the identical trace (zero-tolerance gate: quantization error must
      not flip a single greedy token at these dims).

    The fp32 KERNEL stream is checked bit-identical to the fp32 gather
    stream inline (the exactness oracle) — any divergence raises and
    lands in ``serve_paged_kernel_error`` rather than shipping a wrong
    throughput number.

    Takes the serving model's ``(lcfg, params)`` — it builds its own
    paged pools, so any dims work (bench_serving passes 13B layer dims;
    bench_cpu_basis tiny dims).
    """
    from neuronx_distributed_tpu.inference import CausalLM, ServeEngine
    from neuronx_distributed_tpu.inference.engine import run_trace, synthetic_trace
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

    out = {}
    try:
        page_size = 16
        ppseq = (prompt_len + 256) // page_size
        paged_kw = dict(buckets=(64, prompt_len), max_batch=max_batch,
                        page_size=page_size,
                        page_pool_pages=max_batch * ppseq // 2 + max_batch)
        ktrace = synthetic_trace(
            12, 32000, prompt_lens=(page_size,), max_new_tokens=48,
            mean_interarrival_blocks=0.5,
            shared_prefix_len=prompt_len - page_size, seed=0)

        def krun(lm_):
            # warm every insert program the trace can hit plus the fused
            # block (bench_serving's paged discipline: compiles are
            # process-global, so run ORDER would otherwise silently favor
            # whichever variant ran last)
            for rows in range(1, max_batch + 1):
                for b in (64, prompt_len):
                    lm_._paged_insert_programs(rows, b)
            warm = ServeEngine(lm_, block_steps=fused_steps)
            for item in ktrace[:max_batch]:
                warm.submit(item["prompt"], 2)
            warm.run()
            eng_ = ServeEngine(lm_, block_steps=fused_steps)
            rep_ = run_trace(eng_, ktrace)
            streams = {c.request_id: c.tokens.tolist()
                       for c in eng_.completed}
            return rep_, streams

        # fp32 gather path: the exactness reference AND the greedy oracle
        # for the int8 match rate
        lm_g = CausalLM(lcfg, params, LlamaForCausalLM, **paged_kw)
        lm_g.compile()
        _rep_g, streams_g = krun(lm_g)

        # fp32 kernel path: the throughput claim; its streams must be
        # BIT-identical to the gather's (same fp32 pool bytes, same
        # tokens — only the attention schedule differs)
        lm_k = CausalLM(lcfg, params, LlamaForCausalLM,
                        paged_attn_kernel=True, **paged_kw)
        lm_k.compile()
        rep_k, streams_k = krun(lm_k)
        if streams_k != streams_g:
            raise AssertionError(
                "fp32 kernel streams diverged from the gather oracle")
        out["serve_tokens_per_sec_paged_kernel"] = rep_k["tokens_per_sec"]
        out["serve_paged_kernel_host_ops_per_block"] = \
            rep_k["host_ops_per_block"]

        # int8 pages under the kernel: the sizing ratio (vs the
        # UN-quantized slab — kv_cache_bytes pins the slab basis to
        # config.dtype regardless of page_dtype) + greedy agreement
        lm_i = CausalLM(lcfg, params, LlamaForCausalLM,
                        paged_attn_kernel=True, page_dtype="int8",
                        **paged_kw)
        lm_i.compile()
        kv_i = lm_i.kv_cache_bytes()
        out["paged_hbm_bytes_int8"] = kv_i["kv_bytes"]
        out["paged_hbm_bytes_vs_slab_int8"] = round(
            kv_i["kv_bytes"] / kv_i["kv_slab_bytes"], 3)
        _rep_i, streams_i = krun(lm_i)
        tot = match = 0
        for rid, ref in streams_g.items():
            got = streams_i.get(rid, [])
            tot += max(len(ref), len(got))
            match += sum(1 for a, b_ in zip(ref, got) if a == b_)
        out["serve_greedy_match_rate_int8kv"] = (
            round(match / tot, 3) if tot else None)
        out["serve_paged_kernel_basis"] = (
            f"12 reqs @ 0.5 blocks sharing a {prompt_len - page_size}-"
            f"token cached prefix ({page_size}-token suffix prompts, 48 "
            f"new tokens, fused {fused_steps}-step blocks), page_size "
            f"{page_size}, pool {max_batch * ppseq // 2 + max_batch} "
            f"pages; kernel tok/s = block-sparse paged decode kernel "
            f"(interpret mode on CPU — absolute number is basis-bound); "
            f"int8 ratio = (int8 pools + fp32 per-page scales) / "
            f"un-quantized slab at the same dims; match rate = greedy "
            f"token agreement int8 vs fp32 gather, fp32 kernel checked "
            f"bit-identical to gather inline")
        del lm_g, lm_k, lm_i
    except Exception as e:  # noqa: BLE001 — kernel section additive, never fatal
        out["serve_paged_kernel_error"] = f"{type(e).__name__}: {e}"[:120]
    return out


def bench_async_loop(lcfg, params, prompt_len=128, max_batch=4,
                     fused_steps=4) -> dict:
    """Async double-buffered block loop (ISSUE 19 tentpole evidence), a
    standalone function like :func:`bench_paged_kernel` so
    ``scripts/bench_cpu_basis.py --async-update`` can refresh JUST these
    keys over a committed artifact. Two claims, one trace:

    * ``serve_interblock_gap_ms`` — mean device idle between consecutive
      fused blocks (fetch-end -> next-dispatch-start, read off the
      tracer's dispatch-lane spans by ``interblock_gaps``) with
      ``async_loop=True``. The pipelined loop dispatches block t+1 BEFORE
      fetching block t, so this is ~0 by construction; the sync basis it
      must undercut >= 2x rides the sidecar as
      ``serve_interblock_gap_ms_sync``;
    * ``serve_tokens_per_sec_async_smallK`` — end-to-end async engine
      throughput at SMALL K (``fused_steps`` defaults to 4 here, not
      bench_serving's 16): with few tokens per block the inter-block host
      pass is the dominant per-token cost, so this is where overlapping
      it with device execution pays most. The sync companion rides the
      sidecar as ``serve_tokens_per_sec_sync_smallK``.

    The async streams are checked bit-identical to the sync oracle's
    inline — any divergence raises and lands in ``serve_async_error``
    rather than shipping a wrong throughput number.
    """
    from neuronx_distributed_tpu.inference import CausalLM, ServeEngine
    from neuronx_distributed_tpu.inference.engine import run_trace, synthetic_trace
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

    out = {}
    try:
        lm = CausalLM(lcfg, params, LlamaForCausalLM,
                      buckets=(64, prompt_len), max_batch=max_batch)
        lm.compile()
        atrace = synthetic_trace(
            12, 32000, prompt_lens=(prompt_len,), max_new_tokens=32,
            mean_interarrival_blocks=0.5, seed=0)

        def arun(async_loop):
            warm = ServeEngine(lm, block_steps=fused_steps,
                               async_loop=async_loop)
            for item in atrace[:max_batch]:
                warm.submit(item["prompt"], 2)
            warm.run()
            eng_ = ServeEngine(lm, block_steps=fused_steps,
                               async_loop=async_loop)
            rep_ = run_trace(eng_, atrace)
            streams = {c.request_id: c.tokens.tolist()
                       for c in eng_.completed}
            return rep_, streams

        rep_s, streams_s = arun(False)
        rep_a, streams_a = arun(True)
        if streams_a != streams_s:
            raise AssertionError(
                "async streams diverged from the sync oracle")
        out["serve_interblock_gap_ms"] = rep_a.get(
            "interblock_gap_ms_mean", 0.0)
        out["serve_tokens_per_sec_async_smallK"] = rep_a["tokens_per_sec"]
        out["serve_interblock_gap_ms_sync"] = rep_s.get(
            "interblock_gap_ms_mean")
        out["serve_tokens_per_sec_sync_smallK"] = rep_s["tokens_per_sec"]
        out["serve_fetch_blocked_ms_async"] = rep_a.get(
            "fetch_blocked_ms_mean")
        out["serve_fetch_blocked_ms_sync"] = rep_s.get(
            "fetch_blocked_ms_mean")
        out["serve_async_streams_exact"] = True
        out["serve_async_basis"] = (
            f"12 reqs @ 0.5 blocks ({prompt_len}-token prompts, 32 new "
            f"tokens, fused {fused_steps}-step blocks — SMALL K so the "
            f"inter-block host pass dominates), same trace sync then "
            f"async, streams checked bit-identical inline; gap = mean "
            f"fetch-end->next-dispatch-start on the dispatch lane "
            f"(interblock_gaps), sync basis in "
            f"serve_interblock_gap_ms_sync must be >= 2x the async gap")
        del lm
    except Exception as e:  # noqa: BLE001 — async section additive, never fatal
        out["serve_async_error"] = f"{type(e).__name__}: {e}"[:120]
    return out


def bench_park_resume(lcfg, params, prompt_len=128, max_batch=4,
                      fused_steps=4, n_conv=4) -> dict:
    """Persistent conversation tier (ISSUE 20 tentpole evidence), a
    standalone function like :func:`bench_async_loop` so
    ``scripts/bench_cpu_basis.py --park-update`` can refresh JUST these
    keys over a committed artifact. Three claims, one workload:

    * ``serve_resume_ttft_ms_parked`` — wall ms from ``submit(resume=rid)``
      to the end of the resumed stream's next fused block, for a
      conversation parked to durable storage (manifest verify + sealed
      page adoption + one block — NO re-prefill). The cold contrast basis
      rides the sidecar as ``serve_resume_ttft_ms_cold`` (a from-scratch
      prompt prefill + first block at the same prompt length — the floor
      of what a re-prefill resume would pay);
    * ``serve_resident_bytes_per_idle_conv`` — device+host KV bytes still
      resident per idle PARKED conversation: 0 by construction (park
      evicts every page from the device pool AND the host tier — that is
      the point of the tier); the durable bytes each conversation moved
      to disk ride the sidecar as ``serve_parked_bytes_per_conv_durable``;
    * ``serve_park_resume_exact`` — zero-tolerance: the park → evict →
      resume streams must be bit-identical to the uninterrupted oracle's
      (the resumed stream continues the SAME rng/grammar/KV state, so the
      tier is invisible in the tokens). A divergence raises and lands in
      ``serve_park_error`` rather than shipping wrong numbers.
    """
    import shutil
    import tempfile

    from neuronx_distributed_tpu.inference import CausalLM, ServeEngine
    from neuronx_distributed_tpu.inference.engine import synthetic_trace
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

    out = {}
    park_dir = tempfile.mkdtemp(prefix="bench-park-")
    try:
        page_size = 16
        ppseq = (prompt_len + 64) // page_size + 1
        lm = CausalLM(lcfg, params, LlamaForCausalLM,
                      buckets=(64, prompt_len), max_batch=max_batch,
                      page_size=page_size,
                      page_pool_pages=max_batch * ppseq)
        lm.compile()
        trace = synthetic_trace(n_conv, 32000, prompt_lens=(prompt_len,),
                                max_new_tokens=32,
                                mean_interarrival_blocks=0.0, seed=0)

        def fresh(**kw):
            return ServeEngine(lm, block_steps=fused_steps,
                               rng=jax.random.key(7), **kw)

        eng_o = fresh()
        for item in trace:
            eng_o.submit(item["prompt"], item["max_new_tokens"])
        eng_o.run()
        oracle = {c.request_id: c.tokens.tolist() for c in eng_o.completed}

        eng = fresh(park_dir=park_dir)
        rids = [eng.submit(item["prompt"], item["max_new_tokens"])
                for item in trace]
        for _ in range(2):
            eng.step_block()
        parked = [r for r in rids if eng.park(r) == "parked"]
        pkv = eng.session.paged
        resident = pkv.allocator.in_use() * lm.kv_page_bytes()
        if pkv.tier is not None:
            resident += pkv.tier_pages() * lm.kv_page_bytes_host()
        out["serve_resident_bytes_per_idle_conv"] = int(
            resident // max(len(parked), 1))
        out["serve_parked_bytes_per_conv_durable"] = int(
            sum(eng.park_store.parked_bytes(r) for r in parked)
            // max(len(parked), 1))
        # resume TTFT measured one conversation at a time with nothing
        # else decoding — the span is exactly verify + adoption + 1 block
        ttfts = []
        for r in parked:
            t0 = time.perf_counter()
            eng.submit(resume=r)
            eng.step_block()
            ttfts.append((time.perf_counter() - t0) * 1e3)
        eng.run()
        streams = {c.request_id: c.tokens.tolist() for c in eng.completed}
        # 1.0/0.0 (not bool): bench_regress gates numeric keys only, and
        # this one is zero-tolerance like serve_structured_parse_rate
        out["serve_park_resume_exact"] = 1.0 if streams == oracle else 0.0
        if streams != oracle:
            raise AssertionError(
                "park/resume streams diverged from the uninterrupted "
                "oracle")
        out["serve_resume_ttft_ms_parked"] = round(
            float(np.mean(ttfts)), 3)
        eng_c = fresh()
        t0 = time.perf_counter()
        eng_c.submit(trace[0]["prompt"], 32)
        eng_c.step_block()
        out["serve_resume_ttft_ms_cold"] = round(
            (time.perf_counter() - t0) * 1e3, 3)
        out["serve_park_basis"] = (
            f"{n_conv} convs ({prompt_len}-token prompts, 32 new tokens, "
            f"fused {fused_steps}-step blocks), parked after 2 blocks to "
            f"a tmpdir ConversationParkStore, residency read off the page "
            f"allocator + host tier AFTER park (0 = fully evicted), then "
            f"resumed one at a time (ttft = submit(resume)+1 block wall); "
            f"streams checked bit-identical to the never-parked oracle "
            f"inline; cold basis = fresh prompt prefill + 1 block")
        del lm
    except Exception as e:  # noqa: BLE001 — park section additive, never fatal
        out["serve_park_error"] = f"{type(e).__name__}: {e}"[:120]
    finally:
        shutil.rmtree(park_dir, ignore_errors=True)
    return out


def bench_serving_tp(lcfg, prompt_len=128, max_batch=4,
                     fused_steps=16, tp=2) -> dict:
    """TP-sharded serving section (ISSUE 16 tentpole evidence), a
    standalone function like :func:`bench_structured` so the CPU-basis
    baseline driver (``scripts/bench_cpu_basis.py --tp-update``) can
    refresh JUST these keys over a committed artifact. Three claims:

    * ``serve_tokens_per_sec_tp2`` vs ``serve_tokens_per_sec_tp1`` (and
      their ratio ``serve_tp2_vs_tp1``) — the same paged continuous-
      batching trace on a TP=2 mesh vs the TP=1 baseline. On the CPU
      mesh this measures overhead parity (the per-shard programs plus
      emulated collectives must not stall the pool); on real hardware
      the sharded pool is also the latency win;
    * ``serve_kv_pool_capacity_x_tp`` — per-chip KV pool bytes at TP=1
      divided by per-chip bytes at TP=tp: the capacity-multiplication
      claim (~×tp — logical pages per chip-equivalent multiply, since
      each chip holds only its head-shard of every page);
    * the exactness oracle rides along: both runs' token streams must be
      bit-identical (``serve_tp2_stream_equal``, sidecar) — a divergence
      fails the section.

    Builds its own params per TP world via the trainer's deterministic
    seed-0 init (value-identical across degrees), so any ``lcfg`` whose
    kv-head/vocab counts divide ``tp`` works.
    """
    from neuronx_distributed_tpu.inference import CausalLM, ServeEngine
    from neuronx_distributed_tpu.inference.engine import run_trace, synthetic_trace
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_model,
        neuronx_distributed_config,
    )

    out = {}
    try:
        if len(jax.devices()) < tp:
            raise RuntimeError(
                f"TP section needs >= {tp} devices, have "
                f"{len(jax.devices())} (CPU runs: set "
                f"xla_force_host_platform_device_count)")
        page_size = 16
        new_tokens = 32
        ppseq = -(-(prompt_len + new_tokens + fused_steps) // page_size)
        trace = synthetic_trace(
            12, lcfg.vocab_size, prompt_lens=(prompt_len,),
            max_new_tokens=new_tokens, mean_interarrival_blocks=0.5, seed=0)

        def measure(degree):
            ps.destroy_model_parallel()
            nxd = neuronx_distributed_config(tensor_parallel_size=degree)
            model = initialize_parallel_model(
                nxd, lambda: LlamaForCausalLM(lcfg),
                jnp.zeros((1, 8), jnp.int32))
            lm_ = CausalLM(lcfg, model.params, LlamaForCausalLM,
                           buckets=(prompt_len,), max_batch=max_batch,
                           page_size=page_size,
                           page_pool_pages=max_batch * ppseq + max_batch)
            lm_.compile()
            # warm the whole admission path outside the measured window
            # (bench_structured's discipline: staggered submissions
            # compile every insert width + the fused block first)
            for rows in range(1, max_batch + 1):
                lm_._insert_programs(rows, prompt_len)
            warm = ServeEngine(lm_, block_steps=fused_steps)
            for i, item in enumerate(trace[:max_batch]):
                warm.submit(item["prompt"], 2, arrival_block=i // 2)
            warm.run()
            eng_ = ServeEngine(lm_, block_steps=fused_steps)
            rep = run_trace(eng_, trace)
            streams = {c.request_id: c.tokens.tolist()
                       for c in eng_.completed}
            kv = lm_.kv_cache_bytes()
            return rep, streams, kv

        rep1, s1, kv1 = measure(1)
        rep2, s2, kv2 = measure(tp)
        ps.destroy_model_parallel()
        out["serve_tokens_per_sec_tp1"] = rep1["tokens_per_sec"]
        out[f"serve_tokens_per_sec_tp{tp}"] = rep2["tokens_per_sec"]
        if rep1["tokens_per_sec"] and rep2["tokens_per_sec"]:
            out["serve_tp2_vs_tp1"] = round(
                rep2["tokens_per_sec"] / rep1["tokens_per_sec"], 3)
        out["serve_kv_pool_capacity_x_tp"] = round(
            kv1["kv_bytes"] / kv2["kv_bytes"], 3)
        out["serve_tp2_stream_equal"] = bool(s1 == s2)
        if not out["serve_tp2_stream_equal"]:
            raise RuntimeError(
                "TP-sharded streams diverged from the TP=1 oracle")
        out["serve_tp_basis"] = (
            f"same 12-req paged trace ({prompt_len}-token prompts, "
            f"{new_tokens} new tokens, 0.5-block arrivals, page_size "
            f"{page_size}, K={fused_steps}) served at TP=1 and TP={tp} "
            f"on the {jax.default_backend()} mesh; params born via the "
            f"seed-0 trainer init in each world (value-identical); "
            f"streams bit-compared (equality required); capacity = "
            f"per-chip KV pool bytes TP=1 / TP={tp} "
            f"(kv_cache_bytes()['kv_bytes'], expect ~x{tp})")
    except Exception as e:  # noqa: BLE001 — TP section additive, never fatal
        out["serve_tp2_error"] = f"{type(e).__name__}: {e}"[:120]
    return out


def bench_sched_soak(scales=(1_000, 100_000, 1_000_000),
                     replicas=100) -> dict:
    """Host-only scheduler scaling curve (see the call site above for the
    protocol). Separate function so the mocked bench-report tests and the
    CPU-basis baseline driver can run/patch it without the jax model
    sections."""
    out = {}
    try:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "nxd_soak", os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "scripts", "soak.py"))
        soak = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(soak)
        curve = soak.scaling_curve(scales=tuple(scales), replicas=replicas)
        per = curve["scales"]
        names = {1_000: "1k", 10_000: "10k", 100_000: "100k",
                 1_000_000: "1m"}
        for n in scales:
            tag = names.get(int(n), str(n))
            out[f"router_sched_overhead_us_per_request_{tag}"] = \
                per[str(n)]["router_sched_overhead_us_per_request"]
        biggest = per[str(max(int(n) for n in scales))]
        out["router_sched_overhead_us_per_request"] = \
            biggest["router_sched_overhead_us_per_request"]
        out["router_sched_overhead_scaling_ratio"] = \
            curve["overhead_ratio_max_vs_min_scale"]
        out["soak_rss_mb_per_100k_requests"] = max(
            biggest["rss_mb_per_100k_requests"] or 0.0, 0.0)
        out["soak_rss_mb_peak"] = biggest["rss_mb_peak"]
        out["sched_soak_curve"] = per
        out["sched_soak_basis"] = (
            f"{replicas} sim replicas (SimCausalLM — host-only, zero XLA, "
            f"real paged accounting at page_size 4 / 64 pages), streaming "
            f"router (keep_completions=False, untraced, least_loaded), "
            f"0.8x-saturation Poisson arrivals, 16 new tokens / K=8; "
            f"overhead = total host wall us per completed request (no "
            f"device time exists to hide behind); RSS slope = least-"
            f"squares MB per 100k requests over the final 80% of the "
            f"largest run, clamped at 0")
    except Exception as e:  # noqa: BLE001 — soak section additive, never fatal
        out["sched_soak_error"] = f"{type(e).__name__}: {e}"[:120]
    return out


# the headline subset printed as the FINAL stdout line: short numeric keys
# only, so a 2000-byte tail capture of the run always parses (VERDICT r5
# weak #1: the round-5 capture was tail-truncated to parsed:null). The FULL report —
# long unit strings, per-depth dicts, skip lists — lives in the
# BENCH_REPORT.json sidecar next to this script.
HEADLINE_KEYS = (
    "metric", "value", "vs_baseline", "train_measured",
    "train_fit_residual_ms", "train_vs_baseline_conservative",
    "mfu_7b_projected",
    "ttft_ms_13b_projected_p50fit", "ttft_device_ms_13b_projected",
    "decode_ms_per_token_13b_projected",
    "decode_fused16_ms_per_token_13b_projected",
    "decode_fused16_tokens_per_sec_13b_int8",
    "cp2_zigzag_vs_sp_flash_throughput_16k",
    # spec_round_device_ms (the unfused contrast basis) moved to the
    # sidecar in ISSUE 14 to keep the headline under its 2000-byte tail
    # cap; the fused number and the end-to-end speedup stay gated
    "spec_fused_round_device_ms",
    "spec_speedup_fused_int8draft2L", "spec_fused_acceptance_int8draft2L",
    "spec_acceptance_real_int8draft",
    # serve_insert_fullwidth_ms_1slot (the pre-right-sizing contrast
    # basis) is sidecar-only since ISSUE 14 (headline size cap)
    "serve_tokens_per_sec_cb", "serve_insert_ms_1slot", "serve_insert_ms_4slot",
    "serve_fused_round_device_ms",
    "serve_fused_ms_per_token", "serve_fused_vs_generate_fused16",
    "serve_cold_ttft_ms", "serve_prefix_hit_ttft_ms",
    "serve_prefix_hit_ttft_ratio", "paged_hbm_bytes_vs_slab",
    "serve_tokens_per_sec_paged",
    # paged flash-attention kernel + int8 KV pages (ISSUE 17): kernel-path
    # throughput, the int8-vs-unquantized-slab sizing ratio (<= 0.5 gate)
    # and the zero-tolerance greedy agreement of int8 streams vs the fp32
    # gather oracle; absolute int8 pool bytes and the basis string ride
    # the sidecar (2000-byte headline tail cap)
    "serve_tokens_per_sec_paged_kernel", "paged_hbm_bytes_vs_slab_int8",
    "serve_greedy_match_rate_int8kv",
    # async double-buffered block loop (ISSUE 19): mean device idle
    # between fused blocks (~0 when pipelined — the zero-host-blocking-
    # between-blocks contract) and async throughput at small K; the sync
    # bases (serve_interblock_gap_ms_sync — the >= 2x pin denominator —
    # and serve_tokens_per_sec_sync_smallK), the exactness flag and the
    # basis string ride the sidecar (2000-byte headline tail cap)
    "serve_interblock_gap_ms", "serve_tokens_per_sec_async_smallK",
    # persistent conversation tier (ISSUE 20): resume-from-park TTFT (no
    # re-prefill), per-idle-conversation resident KV bytes after park
    # (0 = fully evicted from device AND host) and the zero-tolerance
    # bit-identity of parked/resumed streams vs the uninterrupted oracle;
    # the cold re-prefill basis (serve_resume_ttft_ms_cold), durable
    # bytes per conversation and the basis string ride the sidecar
    # (2000-byte headline tail cap)
    "serve_resume_ttft_ms_parked", "serve_resident_bytes_per_idle_conv",
    "serve_park_resume_exact",
    "serve_prefix_hit_ttft_ms_tiered", "tier_restore_ms_p99",
    # serve_shed_rate_poolpressure and serve_deadline_miss_rate_noshed
    # (the no-mitigation contrast bases — the tiered shed rate and the
    # shedding miss rate they contrast against both still gate) moved to
    # the sidecar in ISSUE 17 to make room for the paged-kernel keys
    # under the 2000-byte tail cap
    "serve_shed_rate_poolpressure_tiered",
    # serve_itl_p99_ms_unchunked (one-shot-insert contrast basis):
    # sidecar-only since ISSUE 14 (headline size cap)
    "serve_itl_p50_ms", "serve_itl_p99_ms",
    # serve_decode_stall_ms_longprompt, serve_goodput_1x and
    # serve_agg_goodput_2x_n4_rr (contrast bases — the chunked stall, the
    # 2x-vs-1x ratio and the affinity-router number they contrast against
    # all still gate) moved to the sidecar in ISSUE 16 to make room for
    # the TP keys under the 2000-byte tail cap
    "serve_decode_stall_ms_longprompt_chunked",
    "serve_itl_p99_ms_disagg", "serve_decode_stall_ms_longprompt_disagg",
    "serve_goodput_2x_overload", "serve_goodput_2x_vs_1x",
    "serve_deadline_miss_rate_shed",
    "serve_recovery_replay_ms", "serve_tracing_overhead_ratio",
    "serve_agg_goodput_2x_n4",
    "serve_tenant_p99_fairness_ratio", "serve_failover_replay_ms",
    "serve_drain_ms",
    "serve_goodput_autoscale_vs_fixed", "serve_scaleup_time_to_ready_blocks",
    "serve_tokens_per_sec_multilora", "serve_multilora_vs_merged",
    "adapter_switch_overhead_ms",
    "serve_structured_parse_rate", "serve_itl_p50_ms_structured_vs_freeform",
    "grammar_compile_ms",
    # TP-sharded serving (ISSUE 16): the TP2/TP1 speedup ratio and the
    # per-chip pool-capacity multiplication (~xTP, the point of the shard)
    # gate from the headline; the absolute tp1/tp2 throughputs, the
    # bit-equality oracle flag and basis string ride the sidecar (the
    # headline is capped at a 2000-byte tail capture)
    "serve_tp2_vs_tp1", "serve_kv_pool_capacity_x_tp",
    # fleet-scale scheduler soak (ISSUE 14): the 1M-scale overhead, the
    # 1M-vs-1k sub-linearity ratio and the RSS leak slope gate from the
    # headline; the full per-scale curve (1k/100k/1M) rides the sidecar's
    # sched_soak_curve + router_sched_overhead_us_per_request_{1k,100k}
    # (the headline is capped at a 2000-byte tail capture)
    "router_sched_overhead_us_per_request",
    "router_sched_overhead_scaling_ratio",
    "soak_rss_mb_per_100k_requests",
    "ttft_error", "spec_bench_error", "serve_bench_error", "serve_paged_error",
    "serve_chunked_error", "serve_overload_error", "serve_router_error",
    "serve_tier_error", "serve_multilora_error", "serve_disagg_error",
    "serve_autoscale_error", "serve_structured_error", "sched_soak_error",
    "serve_tp2_error", "serve_paged_kernel_error", "serve_async_error",
    "serve_park_error",
)


def runtime_env() -> dict:
    """jax/jaxlib versions + active XLA/runtime flags, recorded in the
    BENCH_REPORT.json sidecar so PROFILE.md's machine-state caveats are
    machine-checkable across runs (two rounds' numbers are only comparable
    when these match). Sidecar-only — never a headline key."""
    import os

    try:
        import jaxlib
        jaxlib_version = getattr(jaxlib, "__version__", None)
    except Exception:  # noqa: BLE001
        jaxlib_version = None
    return {
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib_version,
        "backend": jax.default_backend(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "libtpu_init_args": os.environ.get("LIBTPU_INIT_ARGS", ""),
        "jax_enable_x64": bool(jax.config.jax_enable_x64),
        "jax_disable_most_optimizations": bool(
            getattr(jax.config, "jax_disable_most_optimizations", False)),
    }


def emit_report(report: dict) -> None:
    """Write the full report to the sidecar, print the compact headline line
    LAST (tail-capture-proof artifact protocol). The headline carries a
    pointer to the sidecar so a reader of either finds the other."""
    import os
    from pathlib import Path

    path = os.environ.get("BENCH_REPORT_PATH") or str(
        Path(__file__).resolve().with_name("BENCH_REPORT.json"))
    # the sidecar records its OWN gate set so scripts/bench_regress.py
    # compares two artifacts under the headline-key list each was built
    # with (ast-parsing bench.py is only the fallback for old artifacts)
    report = {**report, "env": runtime_env(),
              "headline_keys": list(HEADLINE_KEYS)}
    try:
        with open(path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
        sidecar = os.path.basename(path)
    except OSError as e:  # read-only checkout: headline still emits
        sidecar = f"unwritable: {e}"[:80]
    headline = {k: report[k] for k in HEADLINE_KEYS if k in report}
    headline["full_report"] = sidecar
    print(json.dumps(headline))


def main():
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:  # CPU smoke fallback so the script always emits a line
        step, state, batch_data, lcfg = build_step(2, 1, 256, False)
        dt, _ = timed_steps(step, state, batch_data, 2)
        emit_report({
            "metric": "cpu_smoke_train_tokens_per_sec",
            "value": round(256 / dt, 1),
            "unit": "tokens/s (tiny model, cpu smoke)",
            "vs_baseline": 0.0,
            "train_measured": False,
        })
        return

    batch, seq = 8, 2048
    tr = bench_train(batch=batch, seq=seq)
    times, mem = tr["times"], tr["mem_L2"]
    tokens = batch * seq
    # catastrophic sweep (every L>=1 depth failed, e.g. a machine state that
    # OOMs even L=1): the projection has no per-layer signal — value and
    # vs_baseline are NULL and train_measured is false (a 0.0 sentinel would
    # silently average into a downstream aggregator, ADVICE r5 low #1) — but
    # the artifact still carries whatever WAS measured (the L=0 step if it
    # ran, and the independent inference/CP/speculation sections below, each
    # already never-fatal).
    measurable = any(L >= 1 for L in times)
    if measurable:
        t_full, train_resid = _depth_fit(times, FULL_LAYERS)
        tok_s_7b = tokens / t_full
        # label must match the basis _depth_fit actually used: a None
        # residual means it fell back to naive per-layer scaling (single
        # surviving depth, or a >=2-depth sweep so noisy the line had
        # non-positive slope / negative intercept)
        lsq_basis = train_resid is not None
    else:
        t_full, train_resid = None, None
        tok_s_7b = None
        lsq_basis = False
    # CONSERVATIVE companion projection: slope from the L>=1 points only.
    # Measured fact (r5): the zero-layer step costs ~50 ms MORE than the
    # L>=1 line's intercept (no layer work to schedule the fixed work
    # against), so a straight LSQ over {0,1,2} tilts optimistic and says so
    # via its residual. The L>=1 slope is the asymptotically-safe per-layer
    # marginal (it cannot shrink below the per-layer weight-traffic
    # roofline, PROFILE.md ceiling argument) — report both, flag the
    # discrepancy, let the reader pick the basis.
    cons = {L: t for L, t in times.items() if L >= 1}
    t_cons = a1_cons = None
    if len(cons) >= 2:
        # one fit feeds BOTH the conservative projection and the
        # L0-deviation gate below — _depth_fit's degenerate fallback would
        # otherwise let the note describe a line the keys didn't use
        b1, a1_cons = _fit_line(cons)
        if b1 > 0 and a1_cons >= 0:
            t_cons = a1_cons + FULL_LAYERS * b1
        else:
            a1_cons = None  # noisy sweep: no conservative basis to offer
    lcfg = tr["lcfg"]  # 7B layer dims from the actual measured config
    flops_7b = flops_l2 = None
    if lcfg is not None:  # None iff build_step never completed at any depth
        dims = (lcfg.hidden_size, lcfg.intermediate_size, lcfg.vocab_size,
                lcfg.num_heads, lcfg.head_dim_)
        flops_7b = model_flops_per_step(FULL_LAYERS, batch, seq, *dims)
        flops_l2 = model_flops_per_step(2, batch, seq, *dims)
    try:
        infer = bench_inference_ttft()
    except Exception as e:  # keep the primary metric printable regardless
        infer = {"ttft_error": f"{type(e).__name__}: {e}"[:200]}
    gc.collect()  # drop any buffers pinned by a failed section's frames
    try:
        # fused ring-attention CP vs SP+flash at equal global tokens
        # (single-chip-scaled; utils/cp_microbench.py), measured in this
        # process — the chip belongs to one. validate_long_seq's --cp rows
        # use the same call — one basis, one estimator (VERDICT r4 #7).
        from neuronx_distributed_tpu.utils.cp_microbench import (
            measure_cp_ratio_isolated,
        )

        cp_row = measure_cp_ratio_isolated(16384, trials=5)
        infer["cp2_zigzag_vs_sp_flash_throughput_16k"] = cp_row["cp_vs_sp_throughput"]
        infer["cp2_zigzag_vs_sp_ici_serial_16k"] = cp_row["cp_vs_sp_throughput_ici_serial"]
        infer["cp2_basis"] = cp_row["note"]
        # estimator provenance: first-try fast mode vs best-of-N vs fallback
        infer["cp2_attempts"] = cp_row["cp_attempts"]
        infer["cp2_isolated"] = cp_row["cp_isolated"]
    except Exception as e:
        infer["cp_bench_error"] = f"{type(e).__name__}: {e}"[:120]
    gc.collect()
    try:
        infer.update(bench_speculation())
    except Exception as e:
        infer["spec_bench_error"] = f"{type(e).__name__}: {e}"[:120]
    gc.collect()
    try:
        # continuous-batching serving engine (ISSUE 2): right-sized insert
        # scaling + fused multi-slot decode window + arrival-trace throughput
        infer.update(bench_serving())
    except Exception as e:
        infer["serve_bench_error"] = f"{type(e).__name__}: {e}"[:120]
    report = {
        "metric": "llama2_7b_train_tokens_per_sec_per_chip",
        "value": None if tok_s_7b is None else round(tok_s_7b, 1),
        "unit": (("tokens/s/chip (7B dims, least-squares step_time(L)=a+b*L "
                  f"over L={sorted(times)} interleaved passes, t_7B=a+32b)")
                 if lsq_basis else
                 (f"tokens/s/chip (7B dims, DEGRADED: naive per-layer scaling "
                  f"from the deepest surviving depth of L={sorted(times)}, "
                  "t_7B=t(L)/L*32 — fixed cost charged per layer; the LSQ fit "
                  "did not happen or degenerated)")
                 if measurable else
                 "tokens/s/chip (UNMEASURED: every L>=1 train depth failed)"),
        "vs_baseline": (None if tok_s_7b is None
                        else round(tok_s_7b / BASELINE_TOK_S_PER_CHIP, 3)),
        "train_measured": measurable,
        "train_fit_depths": sorted(times),
        "train_fit_residual_ms": (None if train_resid is None
                                  else round(train_resid * 1e3, 2)),
        "train_step_time_s_measured": {
            str(L): round(t, 4) for L, t in sorted(times.items())},
        "train_windows_per_depth": {
            str(L): n * tr["windows_per_visit"] for L, n in tr["visits"].items()},
        "batch": batch, "seq": seq,
        "step_memory_bytes_L2": mem,
    }
    if lsq_basis and flops_7b is not None:
        # derived from t_full, so it shares the headline's basis: emit only
        # when that basis is the real LSQ fit (a naive-scaled MFU would
        # masquerade as a fit projection in cross-run dashboards)
        report["mfu_7b_projected"] = round(flops_7b / t_full / V5E_PEAK_BF16, 3)
    if 2 in times:
        if flops_l2 is not None:
            report["mfu_L2_measured"] = round(
                flops_l2 / times[2] / V5E_PEAK_BF16, 3)
        # continuity keys (r1-r4 series)
        report["step_time_L2_s"] = round(times[2], 4)
    if 1 in times:
        report["step_time_L1_s"] = round(times[1], 4)
    if 0 in times:
        report["step_time_L0_s"] = round(times[0], 4)
    if t_cons is not None:
        report["train_tok_s_conservative_Lge1_slope"] = round(tokens / t_cons, 1)
        report["train_vs_baseline_conservative"] = round(
            tokens / t_cons / BASELINE_TOK_S_PER_CHIP, 3)
        if 0 in times and a1_cons is not None:
            # deviation of the measured L=0 step from the L>=1 line's
            # back-extrapolated intercept — the note below is gated on THIS
            # (sign and size), not on the aggregate residual, so an outlier
            # at some other depth can't mis-attribute the misfit to L=0;
            # a1_cons is the SAME intercept the conservative keys used
            l0_dev = times[0] - float(a1_cons)
            report["train_L0_excess_ms"] = round(l0_dev * 1e3, 2)
            # both note texts describe the headline value as the full LSQ,
            # so they only apply when that is actually its basis — after a
            # degenerate fallback the DEGRADED unit string is the one true
            # description and a note would contradict it
            if lsq_basis and l0_dev > 5e-3:
                report["train_fit_note"] = (
                    "the zero-layer step costs more than the L>=1 line's "
                    "back-extrapolated intercept (unamortized fixed work), "
                    "tilting the full LSQ optimistic; the *_conservative "
                    "keys use the L>=1 slope only and are the floor of the "
                    "projection")
            elif lsq_basis and l0_dev < -5e-3:
                report["train_fit_note"] = (
                    "the L=0 point sits BELOW the L>=1 line's intercept: the "
                    "residual is driven by an L>=1 outlier (machine spike "
                    "mid-sweep), so prefer the full-LSQ value over the "
                    "*_conservative keys this run")
    if tr["skipped"]:
        report["train_skipped_depths"] = tr["skipped"]
    report.update(infer)
    emit_report(report)


if __name__ == "__main__":
    from neuronx_distributed_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    main()
