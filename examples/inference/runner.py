"""Llama inference runner + latency benchmark.

TPU-native counterpart of the reference's ``examples/inference/runner.py``
(649 LoC — trace / load-traced / generate / benchmark / check-accuracy) and
``modules/benchmark.py`` (``LatencyCollector`` percentile report :43-71).
Subcommands:

* ``generate`` — compile the bucketed KV-cached CausalLM and decode prompts
  (token ids in, token ids out; pass --hf_checkpoint to serve real weights
  through the HF converter);
* ``benchmark`` — p50/p90/p95/p99 TTFT + per-token decode latency +
  end-to-end throughput per submodel (context-encoding vs token-gen — the
  reference reports the same split per model wrapper);
* ``check-accuracy`` — greedy-token match + logit divergence report vs an
  fp32 cache-free golden (or the fp32 ``transformers`` model with
  --hf_checkpoint) — reference ``check_accuracy``:290 /
  ``check_accuracy_logits``:352;
* ``serve`` — continuous-batching engine over a synthetic arrival trace
  (admission queue, bucketed right-sized inserts, fused K-step multi-slot
  decode — ``ServeEngine``): throughput + queueing/latency report.

Run (13B dims over every attached device; --tp N to choose):
    python examples/inference/runner.py benchmark
One 16 GB chip (7B widths, depth cut to fit):
    python examples/inference/runner.py serve --preset llama2_7b --num_layers 16 --paged
CI smoke:
    python examples/inference/runner.py benchmark --tiny
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_tpu.inference import CausalLM, Sampler
from neuronx_distributed_tpu.kernels import mode
from neuronx_distributed_tpu.models import llama as llama_presets
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.trainer import (
    initialize_parallel_model,
    neuronx_distributed_config,
)
from neuronx_distributed_tpu.utils import get_logger

logger = get_logger("nxd.examples.inference")


def _model_cls(args):
    """Model family selector (reference ships run_llama.py / run_mixtral.py /
    run_dbrx.py as separate scripts; one flag here; OLMoE is Mixtral's
    stack with QK-norm and an un-renormalised router, models/olmoe.py)."""
    if args.model in ("mixtral", "dbrx"):
        from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM

        return MixtralForCausalLM
    if args.model == "olmoe":
        from neuronx_distributed_tpu.models.olmoe import OlmoeForCausalLM

        return OlmoeForCausalLM
    return LlamaForCausalLM


# the published Llama shapes models/llama.py ships, by the name --preset takes
LLAMA_PRESETS = ("llama2_7b", "llama2_13b", "llama2_70b", "llama3_8b",
                 "llama31_8b", "llama3_70b")


def _tp(args) -> int:
    """--tp, else every device this process sees (--tiny: the 2-way smoke)."""
    return args.tensor_parallel_size or (2 if args.tiny else jax.device_count())


def build_config(args):
    """The family's config at --tiny or published widths; ``--num_layers``
    cuts DEPTH only (what one chip holds), never a width."""
    cfg = _family_config(args)
    if getattr(args, "num_layers", None):
        import dataclasses

        cfg = dataclasses.replace(cfg, num_layers=args.num_layers)
    return cfg


def _family_config(args):
    family = args.model
    if family in ("mixtral", "dbrx"):
        from neuronx_distributed_tpu.models.mixtral import MixtralConfig, dbrx, mixtral_8x7b

        if args.tiny:
            return MixtralConfig(
                vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
                num_heads=4, num_kv_heads=4, max_seq_len=256, dtype=jnp.float32,
                use_flash_attention=False, num_experts=4, top_k=2,
            )
        preset = dbrx if family == "dbrx" else mixtral_8x7b
        return preset(max_seq_len=args.max_seq_len, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, remat_policy=None)
    if family == "olmoe":
        from neuronx_distributed_tpu.models.olmoe import OlmoeConfig, olmoe_1b_7b

        if args.tiny:
            return OlmoeConfig(
                vocab_size=512, hidden_size=64, intermediate_size=32, num_layers=2,
                num_heads=4, num_kv_heads=4, max_seq_len=256, dtype=jnp.float32,
                use_flash_attention=False, num_experts=16, top_k=4,
            )
        return olmoe_1b_7b(max_seq_len=args.max_seq_len, dtype=jnp.bfloat16,
                           param_dtype=jnp.bfloat16, remat_policy=None)
    if args.tiny:
        return LlamaConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
            num_heads=4, num_kv_heads=4, max_seq_len=256, dtype=jnp.float32,
            use_flash_attention=False,
        )
    preset = getattr(llama_presets, getattr(args, "preset", None) or "llama2_13b")
    return preset(
        max_seq_len=args.max_seq_len, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        remat_policy=None,
    )


def build_model(args, cfg=None):
    """(CausalLM, config) for the flags in ``args``; ``cfg`` replaces the
    config the flags name (chip_smoke.py's rehearsal shrinks the widths)."""
    cfg = cfg or build_config(args)
    nxd_config = neuronx_distributed_config(
        tensor_parallel_size=_tp(args)
    )
    ids = jnp.zeros((1, 8), jnp.int32)
    if args.hf_checkpoint:
        # family-generic conversion (reference checkpoint_converter.py:20 is
        # model-generic): llama, mixtral, and dbrx layouts
        import dataclasses

        from flax import linen as nn

        from neuronx_distributed_tpu.converters.hf import FAMILIES
        from neuronx_distributed_tpu.converters.hf_llama import load_hf_safetensors
        from neuronx_distributed_tpu.parallel import mesh as ps
        from neuronx_distributed_tpu.parallel.partitioning import specs_to_shardings

        fam = FAMILIES[args.model]
        cfg = dataclasses.replace(
            fam.config_from_hf(args.hf_checkpoint), max_seq_len=args.max_seq_len,
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
            use_flash_attention=mode.flash_where_compiled(logger.info),
        )
        if not ps.model_parallel_is_initialized():
            ps.initialize_model_parallel(
                tensor_model_parallel_size=nxd_config["tensor_parallel_size"]
            )
        # no throwaway random init: abstract-eval for the sharding specs,
        # then place the converted HF weights directly
        module = _model_cls(args)(cfg)
        abstract = jax.eval_shape(lambda: module.init(jax.random.key(0), ids))
        specs = nn.get_partition_spec(abstract)["params"]
        params = fam.hf_to_nxd(load_hf_safetensors(args.hf_checkpoint), cfg)
        params = jax.device_put(params, specs_to_shardings(specs, ps.get_mesh()))
    else:
        model = initialize_parallel_model(nxd_config, lambda: _model_cls(args)(cfg), ids)
        params = model.params
    buckets = (64, 128) if args.tiny else tuple(
        b for b in (128, 512, 2048, 4096) if b < cfg.max_seq_len
    )
    if getattr(args, "quantize", False):
        # int8 weight-only serving (reference run_llama_quantized.py): the
        # quantized tree feeds the model DIRECTLY — the parallel layers
        # dequantize {'qweight','scale'} leaves in-layer (inside the layer
        # scan), so int8 is what HBM holds and the convert fuses into each
        # layer's matmuls instead of materializing the whole bf16 stack
        # per step (dequantize_leaf; measured ~3x per-layer decode win)
        from neuronx_distributed_tpu.quantization.core import quantize_params

        params = quantize_params(params)
    paged_kw = {}
    # the storage knob implies paged mode: `serve --kv_dtype int8` alone
    # gets the page pool it requires
    if getattr(args, "kv_dtype", None):
        if args.cmd != "serve":
            raise SystemExit("--kv_dtype applies to the serve subcommand only")
        args.paged = True
    if getattr(args, "paged", False):
        if args.cmd != "serve":
            raise SystemExit("--paged applies to the serve subcommand only "
                             "(generate/benchmark run the contiguous path)")
        paged_kw = dict(page_size=args.page_size,
                        page_pool_pages=args.page_pool_pages or None,
                        prefix_cache=not args.no_prefix_cache,
                        page_dtype=getattr(args, "kv_dtype", None))
    if getattr(args, "adapters", 0) > 0:
        # multi-LoRA serving pool: N demo adapters share this one base
        # model via per-slot batched low-rank corrections (S-LoRA); the
        # pool holds --adapter_pool_slots device-resident adapters
        # (identity slot included) with LRU churn beyond that
        if args.cmd != "serve":
            raise SystemExit("--adapters applies to the serve subcommand")
        if getattr(args, "quantize", False):
            raise SystemExit("--adapters with --quantize is not supported "
                             "(adapters factorize the fp32 base kernels)")
        paged_kw.update(
            lora_rank=args.adapter_rank,
            lora_slots=args.adapter_pool_slots or args.adapters + 1)
    if getattr(args, "grammar_frac", 0.0) > 0:
        # structured decoding: the grammar pool's (states, vocab) mask/next
        # tables ride the fused scan as inputs; --grammar_pool_slots caps
        # device residency (identity slot included) with LRU churn beyond
        if args.cmd != "serve":
            raise SystemExit("--grammar_frac applies to the serve "
                             "subcommand")
        paged_kw.update(
            grammar_slots=(args.grammar_pool_slots
                           or args.grammars + 1),
            grammar_states=args.grammar_states)
    lm = CausalLM(cfg, params, _model_cls(args),
                  buckets=buckets, max_batch=args.max_batch, **paged_kw)
    return lm, cfg


# the runner's demo grammar menu (serve --grammar_frac): g0 a bounded
# integer, g1 a compact JSON object (lowered from a schema), g2 a
# function-call shape — cycled over the constrained share of the trace
DEMO_GRAMMARS = (
    {"regex": "-?[0-9]{1,8}"},
    {"json_schema": {"type": "object", "properties": {
        "name": {"type": "string"}, "count": {"type": "integer"},
        "ok": {"type": "boolean"}}}},
    {"regex": '(get|set)\\("[a-z]{1,12}"\\)'},
)


def cmd_generate(args) -> None:
    lm, cfg = build_model(args)
    rs = np.random.RandomState(args.seed)
    b = min(args.max_batch, 2)
    prompt_len = 16 if args.tiny else 128
    prompts = rs.randint(1, cfg.vocab_size, (b, prompt_len)).astype(np.int32)
    t0 = time.perf_counter()
    lm.compile()
    logger.info("compiled in %.1fs", time.perf_counter() - t0)
    result = lm.generate(
        prompts, max_new_tokens=args.max_new_tokens,
        sampler=Sampler(greedy=not args.sample, temperature=args.temperature,
                        top_k=args.top_k or None,
                        top_p=args.top_p if args.top_p < 1.0 else None),
        rng=jax.random.key(args.seed),
        fused_chunk=args.fused_chunk,
    )
    for i, (toks, n) in enumerate(zip(result.tokens, result.lengths)):
        print(json.dumps({"prompt": i, "generated": toks[:n].tolist()}))


def percentiles(ts) -> dict:
    """The reference benchmark's latency report (benchmark.py:55-71)."""
    arr = np.asarray(ts) * 1e3
    return {
        "p50_ms": round(float(np.percentile(arr, 50)), 2),
        "p90_ms": round(float(np.percentile(arr, 90)), 2),
        "p95_ms": round(float(np.percentile(arr, 95)), 2),
        "p99_ms": round(float(np.percentile(arr, 99)), 2),
        "p100_ms": round(float(np.max(arr)), 2),
    }


def cmd_benchmark(args) -> None:
    lm, cfg = build_model(args)
    lm.compile()
    rs = np.random.RandomState(args.seed)
    prompt_len = 16 if args.tiny else args.prompt_len
    bucket = lm._bucket_for(prompt_len)
    prompt = np.zeros((lm.max_batch, bucket), np.int32)
    prompt[:, :prompt_len] = rs.randint(1, cfg.vocab_size, (lm.max_batch, prompt_len))

    # context encoding (TTFT): prefill + first-token argmax fetched to host
    ttft = []
    for _ in range(args.trials):
        t0 = time.perf_counter()
        logits, cache = lm._prefill[bucket](lm.params, jnp.asarray(prompt))
        int(jnp.argmax(logits[0, prompt_len - 1]))  # host fetch = sync
        ttft.append(time.perf_counter() - t0)

    # token generation: chained decode steps
    tok = jnp.zeros((lm.max_batch, 1), jnp.int32)
    logits, cache = lm._decode(lm.params, cache, tok)
    jax.block_until_ready(logits)
    decode = []
    for _ in range(args.decode_steps):
        t0 = time.perf_counter()
        logits, cache = lm._decode(lm.params, cache, tok)
        float(logits[0, 0, 0])
        decode.append(time.perf_counter() - t0)

    report = {
        "model": args.model + ("_tiny" if args.tiny else ""),
        "tp": _tp(args),
        "batch": lm.max_batch,
        "prompt_len": prompt_len,
        "context_encoding": percentiles(ttft),
        "token_generation": percentiles(decode),
        "decode_tokens_per_sec": round(lm.max_batch / float(np.median(decode)), 1),
    }

    if args.fused_chunk > 1:
        # fused K-step decode (one program per K tokens): the serving fast
        # path; report per-token time on the same percentile surface
        fused = lm.compile_decode_fused(args.fused_chunk)
        _, cache = lm._prefill[bucket](lm.params, jnp.asarray(prompt))
        rng = jax.random.key(args.seed)
        done = jnp.zeros((lm.max_batch,), bool)
        toks, cache, tok, rng, done = fused(lm.params, cache, tok, rng, done)
        jax.block_until_ready(toks)
        fused_ts = []
        for _ in range(max(1, args.decode_steps // args.fused_chunk)):
            t0 = time.perf_counter()
            toks, cache, tok, rng, done = fused(lm.params, cache, tok, rng, done)
            int(np.asarray(toks)[-1, 0])
            fused_ts.append((time.perf_counter() - t0) / args.fused_chunk)
        report["token_generation_fused"] = percentiles(fused_ts)
        report["fused_chunk"] = args.fused_chunk
        report["decode_tokens_per_sec_fused"] = round(
            lm.max_batch / float(np.median(fused_ts)), 1)
    print(json.dumps(report))


def cmd_serve(args) -> None:
    """Continuous-batching serving over a synthetic arrival trace (the
    tentpole serving entrypoint): requests arrive over virtual time
    (exponential inter-arrivals, in decode blocks), the scheduler admits
    them into KV-cache slots through bucketed right-sized prefills, and the
    whole slot pool advances ``--fused_steps`` tokens per device dispatch
    (``CausalLM.compile_session_decode_fused``). ``--stepwise`` replays the
    identical schedule through per-token dispatches — the baseline the
    fused path is measured against (token streams are bit-identical)."""
    import os

    from neuronx_distributed_tpu.inference.engine import ServeEngine
    from neuronx_distributed_tpu.inference.replay import (
        run_router_trace,
        run_trace,
        synthetic_trace,
    )
    from neuronx_distributed_tpu.inference.faults import resolve_fault_plan

    from neuronx_distributed_tpu.inference.router import Router

    # TP-sharded serving (serve --tp N): the mesh is built by build_model;
    # gate the divisibility constraints HERE, before any compile — a head
    # or vocab count that does not divide TP would silently fall back to
    # replicated leaves (degraded capacity), which a `--tp N` request
    # should refuse loudly instead
    tp = _tp(args)
    if tp > 1:
        cfg0 = build_config(args)
        for dim_name, dim in (("num_kv_heads", cfg0.num_kv_heads),
                              ("num_heads", cfg0.num_heads),
                              ("vocab_size", cfg0.vocab_size)):
            if dim % tp:
                raise SystemExit(
                    f"serve --tp {tp}: {dim_name}={dim} is not divisible "
                    f"by the TP degree — the KV pool / grammar tables "
                    f"cannot shard evenly (pick a TP that divides heads "
                    f"and vocab)")

    lm, cfg = build_model(args)
    lm.compile()

    def make_adapters():
        # N deterministic demo adapters over the base params (rank r,
        # nonzero B so each adapter genuinely moves the logits) — the
        # per-user-fine-tune workload; real deployments register trained
        # init_lora trees the same way
        from neuronx_distributed_tpu.lora import LoraConfig, init_lora

        acfg = LoraConfig(r=args.adapter_rank)
        out = {}
        for i in range(args.adapters):
            ad = init_lora(lm.params, acfg, jax.random.key(1000 + i))
            out[f"a{i}"] = {
                k: {"lora_a": v["lora_a"],
                    "lora_b": 0.02 * jax.random.normal(
                        jax.random.fold_in(jax.random.key(2000 + i), j),
                        v["lora_b"].shape, jnp.float32)}
                for j, (k, v) in enumerate(sorted(ad.items()))}
        return out, acfg

    adapter_reg = None
    if args.adapters:
        adapter_reg, adapter_cfg = make_adapters()
    # structured decoding: n demo grammars (regex + JSON-schema) cycled
    # over --grammar_frac of the trace; admission pins each request's
    # token-DFA tables in the device-resident pool (LRU churn past
    # --grammar_pool_slots), the fused scan enforces the mask per step
    grammar_reg = None
    if getattr(args, "grammar_frac", 0.0) > 0:
        grammar_reg = {f"g{i}": DEMO_GRAMMARS[i % len(DEMO_GRAMMARS)]
                       for i in range(args.grammars)}
    # host-memory KV tier (paged + prefix cache only): sized in pages from
    # --host_tier_bytes via the per-page KV footprint; 0 = auto at 2x the
    # device pool (pool pressure then spills instead of shedding)
    tier_pages = 0
    if lm.paged and not args.no_prefix_cache and not args.no_host_tier:
        if args.host_tier_bytes > 0:
            # host tier stores GLOBAL-width pages (gather-at-seal), so the
            # budget divides by the host/handoff page unit, not the
            # per-shard HBM unit
            tier_pages = max(1, args.host_tier_bytes
                             // lm.kv_page_bytes_host())
        else:
            tier_pages = 2 * lm.config.page_pool_pages
    # SLO objectives (observability/slo.py): declarative TTFT/ITL targets
    # evaluated with multi-window burn rates each block; alerts land on the
    # trace and in serve_slo_alerts_total. The completion objective rides
    # along whenever any SLO flag is set.
    slos = None
    if args.slo_ttft_ms or args.slo_itl_ms or args.scale_slo_ms:
        from neuronx_distributed_tpu.observability import default_slos

        # --scale_slo_ms doubles as a TTFT objective: its burn alerts are
        # what the autoscaler's slo_burn signal latches on
        slos = default_slos(ttft_ms=args.slo_ttft_ms or args.scale_slo_ms,
                            itl_ms=args.slo_itl_ms, target=args.slo_target)
    # SLO-driven autoscaling (inference/autoscale.py): the policy runs in
    # the router's block loop and mutates fleet membership live — scale-up
    # spawns replicas (warm from parked snapshots), scale-down drains and
    # parks them; on --disagg each role pool scales independently under
    # the same policy knobs (min/max apply per pool)
    autoscaler = None
    if args.autoscale:
        from neuronx_distributed_tpu.inference.autoscale import (
            Autoscaler, AutoscalePolicy,
        )

        max_reps = args.max_replicas or max(args.replicas,
                                            args.min_replicas + 1)
        autoscaler = Autoscaler(AutoscalePolicy(
            min_replicas=args.min_replicas,
            max_replicas=max_reps,
            backlog_high_blocks=args.scale_up_backlog,
            up_patience_blocks=args.scale_patience_blocks,
            down_utilization=args.scale_down_util,
            down_patience_blocks=args.scale_down_idle_blocks,
            cooldown_blocks=args.scale_cooldown_blocks))
    eng_kw = dict(block_steps=args.fused_steps, fused=not args.stepwise,
                  async_loop=args.async_loop,
                  prefill_chunk_tokens=args.prefill_chunk_tokens,
                  max_queue=args.max_queue, shed_policy=args.shed_policy,
                  block_time_ms=args.block_time_ms,
                  host_tier_pages=tier_pages,
                  park_idle_blocks=args.park_idle_blocks,
                  park_dir=args.park_dir,
                  slos=slos,
                  # the incident trace slice reads the tracer, so arming
                  # the flight recorder turns structured tracing on too
                  trace=bool(args.trace_out) or bool(args.incident_dir),
                  incident_dir=args.incident_dir)

    def export_observability(engine) -> None:
        # written AFTER the run so the trace covers the whole timeline; the
        # trace file is Perfetto-loadable Chrome trace-event JSON, the
        # metrics file Prometheus text (or a JSON snapshot for .json paths)
        if args.trace_out:
            engine.tracer.export_chrome(args.trace_out)
        if args.metrics_out:
            engine.metrics.dump(args.metrics_out)

    def observability_report(engine) -> dict:
        # SLO/incident surface appended to the serve report: per-objective
        # compliance + alert counts, and the flight-recorder bundle paths
        out = {}
        if getattr(engine, "_slo", None) is not None:
            out["slo"] = engine.slo_status()
        rec = getattr(engine, "incident", None)
        if rec is not None:
            out["incidents"] = {
                "bundles": rec.bundles,
                "suppressed": rec.suppressed,
            }
        return out
    # crash recovery: a snapshot file surviving at startup means the
    # previous serve died mid-trace — restore it and finish those streams
    # (bit-identical from the interruption point) instead of starting over
    if args.snapshot_path and os.path.exists(args.snapshot_path):
        engine = ServeEngine.from_snapshot(
            lm, args.snapshot_path,
            adapters=(None if adapter_reg is None else
                      {n: (ad, adapter_cfg)
                       for n, ad in adapter_reg.items()}),
            grammars=grammar_reg,
            **eng_kw)
        completions = engine.run()
        export_observability(engine)
        os.remove(args.snapshot_path)
        print(json.dumps({
            "recovered": True,
            "restored_requests": engine.stats["restored_requests"],
            "requests_completed": len(completions),
            "total_generated_tokens": int(sum(len(c.tokens)
                                              for c in completions)),
        }))
        return
    prompt_lens = ((8, 12, 16) if args.tiny
                   else (64, min(128, args.prompt_len), args.prompt_len))
    trace = synthetic_trace(
        args.num_requests, cfg.vocab_size, prompt_lens=prompt_lens,
        max_new_tokens=args.max_new_tokens,
        mean_interarrival_blocks=args.mean_interarrival,
        shared_prefix_len=args.shared_prefix_len,
        prefix_families=args.prefix_families,
        long_prompt_frac=args.long_prompt_frac,
        long_prompt_len=args.long_prompt_len,
        ttft_deadline_ms=args.ttft_deadline_ms,
        deadline_ms=args.deadline_ms,
        tenants=args.tenants,
        tenant_skew=args.tenant_skew,
        adapters=args.adapters,
        adapter_skew=args.adapter_skew,
        grammar_frac=args.grammar_frac,
        grammars=tuple(grammar_reg) if grammar_reg else (),
        diurnal=args.diurnal,
        diurnal_period_blocks=args.diurnal_period_blocks,
        burst_every=args.burst_every,
        burst_mult=args.burst_mult,
        seed=args.seed,
    )
    if args.replicas > 1 or args.autoscale:
        # multi-replica front door: N ServeEngine replicas (one shared lm,
        # N sessions) behind the Router — prefix-affinity placement,
        # per-tenant WFQ, heartbeat failover, graceful drain.
        # --crash_replica_at B injects one replica crash (the last
        # replica) at router block B: the CI smoke's failover gate.
        # --disagg splits the fleet into roles (DisaggRouter): the first
        # --prefill_replicas workers run only insert/extend programs and
        # hand finished KV pages to the decode workers through checksummed
        # handoffs — decode ITL with ZERO prefill sharing.
        crash_at = ([(args.crash_replica_at, args.replicas - 1)]
                    if args.crash_replica_at is not None else ())
        if args.disagg:
            from neuronx_distributed_tpu.inference.disagg import DisaggRouter
            from neuronx_distributed_tpu.inference.replay import (
                run_disagg_trace,
            )

            if not lm.paged:
                raise SystemExit("--disagg requires --paged (the handoff "
                                 "moves KV as physical pages)")
            # warm the whole migration path (insert widths, the fused
            # block, AND the adoption-side page-write programs) outside
            # the measured run — cmd_generate's discipline; the decode
            # clock must time steady-state blocks, not first-call compiles
            warm_r = DisaggRouter(
                lm, 2, prefill_replicas=1,
                block_steps=args.fused_steps, fused=not args.stepwise,
                rng=jax.random.key(args.seed))
            for item in trace[: min(len(trace), lm.max_batch)]:
                warm_r.submit(item["prompt"], 2)
            warm_r.run(max_blocks=200)
            del warm_r
            router = DisaggRouter(
                lm, args.replicas, prefill_replicas=args.prefill_replicas,
                rng=jax.random.key(args.seed), crash_at=crash_at,
                autoscaler=autoscaler,
                faults=resolve_fault_plan(args.fault_plan), **eng_kw)
            if grammar_reg:
                for n, spec in grammar_reg.items():
                    router.register_grammar(n, **spec)
            report = run_disagg_trace(router, trace)
        else:
            # an autoscaled fleet STARTS at the policy floor and grows on
            # demand; a fixed fleet starts (and stays) at --replicas
            start_n = args.min_replicas if args.autoscale else args.replicas
            router = Router(lm, start_n, rng=jax.random.key(args.seed),
                            crash_at=crash_at, autoscaler=autoscaler,
                            faults=resolve_fault_plan(args.fault_plan),
                            **eng_kw)
            if adapter_reg:
                for n, ad in adapter_reg.items():
                    router.register_adapter(n, ad, adapter_cfg)
            if grammar_reg:
                for n, spec in grammar_reg.items():
                    router.register_grammar(n, **spec)
            report = run_router_trace(router, trace)
        if args.trace_out:
            router.tracer.export_chrome(args.trace_out)
        if args.metrics_out:
            router.metrics.dump(args.metrics_out)
        report.update(observability_report(router))
        if slos:
            report["slo"] = {f"replica{i}": eng.slo_status()
                             for i, eng in enumerate(router.engines)}
        report.update({
            "model": args.model + ("_tiny" if args.tiny else ""),
            "max_batch": lm.max_batch,
            "num_requests": args.num_requests,
        })
        print(json.dumps(report))
        return
    engine = ServeEngine(lm, rng=jax.random.key(args.seed),
                         faults=resolve_fault_plan(args.fault_plan), **eng_kw)
    if adapter_reg:
        for n, ad in adapter_reg.items():
            engine.register_adapter(n, ad, adapter_cfg)
    if grammar_reg:
        for n, spec in grammar_reg.items():
            engine.register_grammar(n, **spec)
    # warm every program the trace will hit (all insert widths per bucket +
    # the fused block) OUTSIDE the timed window — cmd_generate's discipline.
    # Paged mode compiles its insert programs lazily per suffix width; the
    # warm engine run below covers the widths the trace produces.
    if not lm.paged:
        for s in sorted({len(item["prompt"]) for item in trace}):
            for rows in range(1, lm.max_batch + 1):
                lm._insert_programs(rows, lm._bucket_for(s))
    warm = ServeEngine(lm, block_steps=args.fused_steps,
                       fused=not args.stepwise,
                       prefill_chunk_tokens=args.prefill_chunk_tokens,
                       rng=jax.random.key(args.seed))
    for item in trace[: min(len(trace), lm.max_batch)]:
        warm.submit(item["prompt"], 2)
    warm.run()
    report = run_trace(engine, trace, snapshot_path=args.snapshot_path)
    export_observability(engine)
    report.update(observability_report(engine))
    report.update({
        "model": args.model + ("_tiny" if args.tiny else ""),
        "max_batch": lm.max_batch,
        "num_requests": args.num_requests,
    })
    print(json.dumps(report))


def cmd_check_accuracy(args) -> None:
    """Correctness gate (reference runner.py ``check_accuracy``:290 +
    ``check_accuracy_logits``:352): the SERVING stack's greedy continuation
    and logits are compared against a golden — an fp32 run of the same params
    through the plain (cache-free) forward, or, with ``--hf_checkpoint``, the
    fp32 ``transformers`` model itself. Reports the greedy match length,
    first-divergence position, and teacher-forced logit max-abs-diff; exits
    nonzero when tokens diverge (the reference asserts the same)."""
    import dataclasses

    lm, cfg = build_model(args)
    lm.compile()
    rs = np.random.RandomState(args.seed)
    prompt_len = 16 if args.tiny else min(args.prompt_len, 128)
    prompt = rs.randint(1, cfg.vocab_size, (1, prompt_len)).astype(np.int32)
    if lm.max_batch > 1:
        prompt = np.broadcast_to(prompt, (lm.max_batch, prompt_len)).copy()

    result = lm.generate(prompt, max_new_tokens=args.max_new_tokens,
                         sampler=Sampler(greedy=True), rng=jax.random.key(0))
    served = np.asarray(result.tokens[0][: int(result.lengths[0])])
    full_seq = np.concatenate([prompt[0], served])

    # ---- golden forward (one teacher-forced call reused per decode step) --
    if args.hf_checkpoint:
        import torch
        from transformers import AutoModelForCausalLM

        hf_model = AutoModelForCausalLM.from_pretrained(
            args.hf_checkpoint, torch_dtype=torch.float32)
        hf_model.eval()

        def golden_forward(ids_row: np.ndarray) -> np.ndarray:
            with torch.no_grad():
                return hf_model(torch.from_numpy(ids_row[None])).logits.numpy()[0]

        golden_name = "transformers_fp32"
    else:
        f32_cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                                      param_dtype=jnp.float32)
        module = _model_cls(args)(f32_cfg)
        from neuronx_distributed_tpu.quantization.core import dequantize_params

        # float golden: undo any serving transform / int8 quantization first
        base = (lm.param_transform(lm.params) if lm.param_transform
                else dequantize_params(lm.params, jnp.float32))
        params32 = jax.tree.map(lambda p: jnp.asarray(p, jnp.float32), base)
        fwd = jax.jit(lambda ids: module.apply({"params": params32}, ids))

        def golden_forward(ids_row: np.ndarray) -> np.ndarray:
            return np.asarray(fwd(jnp.asarray(ids_row[None]))[0], np.float32)

        golden_name = "fp32"

    # ---- one teacher-forced golden pass over prompt+served ---------------
    golden_logits = golden_forward(full_seq)

    # greedy match derived from the SAME pass: the golden's deterministic
    # continuation equals `served` exactly until the first position k where
    # argmax(golden_logits[prompt_len-1+k]) != served[k] (while prefixes
    # agree, teacher-forcing on full_seq IS the golden's autoregression) —
    # no per-token golden forwards / per-length recompiles needed
    match_len = 0
    for k, tok in enumerate(served.tolist()):
        if int(np.argmax(golden_logits[prompt_len - 1 + k])) != tok:
            break
        match_len += 1
    diverged = match_len < len(served)
    bucket = lm._bucket_for(len(full_seq))
    padded = np.zeros((lm.max_batch, bucket), np.int32)
    padded[:, : len(full_seq)] = full_seq
    served_logits = np.asarray(
        lm._prefill[bucket](lm.params, jnp.asarray(padded))[0][0, : len(full_seq)],
        np.float32)
    diff = np.abs(served_logits - golden_logits)
    argmax_mismatch = np.nonzero(
        served_logits.argmax(-1) != golden_logits.argmax(-1))[0]

    report = {
        "golden": golden_name,
        "prompt_len": int(prompt_len),
        "generated": len(served.tolist()),
        "greedy_match": not diverged,
        "match_len": match_len,
        "first_divergence": match_len if diverged else -1,
        "logit_max_abs_diff": round(float(diff.max()), 6),
        "logit_mean_abs_diff": round(float(diff.mean()), 6),
        "argmax_first_mismatch_pos": (int(argmax_mismatch[0])
                                      if argmax_mismatch.size else -1),
        "positions_checked": int(len(full_seq)),
    }
    print(json.dumps(report))
    if diverged:
        raise SystemExit(1)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    sub = parser.add_subparsers(dest="cmd", required=True)
    for name in ("generate", "benchmark", "check-accuracy", "serve"):
        p = sub.add_parser(name)
        p.add_argument("--tensor_parallel_size", "--tp", type=int, default=None,
                       help="default: every device this process sees")
        p.add_argument("--tiny", action="store_true")
        p.add_argument("--preset", choices=LLAMA_PRESETS, default="llama2_13b",
                       help="--model llama: which published shape to build")
        p.add_argument("--num_layers", type=int, default=None,
                       help="cut the model's depth to what the chip holds "
                            "(widths stay the published ones)")
        p.add_argument("--hf_checkpoint", type=str, default=None)
        p.add_argument("--max_seq_len", type=int, default=4096)
        p.add_argument("--max_batch", type=int, default=1)
        p.add_argument("--max_new_tokens", type=int, default=32)
        p.add_argument("--prompt_len", type=int, default=2048)
        p.add_argument("--trials", type=int, default=10)
        p.add_argument("--decode_steps", type=int, default=50)
        p.add_argument("--sample", action="store_true",
                       help="sample with temperature/top_k/top_p (default greedy)")
        p.add_argument("--temperature", type=float, default=1.0)
        p.add_argument("--top_k", type=int, default=0)
        p.add_argument("--top_p", type=float, default=1.0)
        p.add_argument("--seed", type=int, default=0)
        p.add_argument("--fused_chunk", type=int, default=0,
                       help="K>1: decode in K-step fused device programs "
                            "(one dispatch per K tokens; any sampler, "
                            "per-token EOS)")
        p.add_argument("--fused_steps", type=int, default=8,
                       help="serve: K decode steps per device dispatch for "
                            "the whole slot pool (the fused-K knob)")
        p.add_argument("--stepwise", action="store_true",
                       help="serve: per-token dispatch baseline (same "
                            "schedule, bit-identical tokens)")
        p.add_argument("--async", dest="async_loop", action="store_true",
                       help="serve: pipeline the fused block loop — "
                            "dispatch block t+1 before fetching block t, "
                            "so the host scheduling pass overlaps device "
                            "execution (requires fused mode; streams stay "
                            "bit-identical to the sync loop)")
        p.add_argument("--prefill_chunk_tokens", type=int, default=0,
                       help="serve: C>0 prefills prompts longer than C in "
                            "C-token chunks interleaved with decode blocks "
                            "(stall-free batching; bit-identical streams). "
                            "Smaller C tightens live streams' inter-token "
                            "latency, larger C shortens new-request TTFT")
        p.add_argument("--long_prompt_frac", type=float, default=0.0,
                       help="serve: fraction of trace requests carrying a "
                            "long prompt (heavy-tailed interference "
                            "workload; see --long_prompt_len)")
        p.add_argument("--long_prompt_len", type=int, default=0,
                       help="serve: prompt length of the long-tail requests "
                            "when --long_prompt_frac > 0")
        p.add_argument("--num_requests", type=int, default=8,
                       help="serve: synthetic arrival-trace length")
        p.add_argument("--mean_interarrival", type=float, default=0.5,
                       help="serve: mean request inter-arrival time in "
                            "decode blocks (exponential)")
        p.add_argument("--paged", action="store_true",
                       help="serve: paged KV cache (block-table page pool + "
                            "shared-prefix reuse instead of the slot slab)")
        p.add_argument("--page_size", type=int, default=16,
                       help="serve --paged: tokens per KV page (must divide "
                            "max_seq_len)")
        p.add_argument("--page_pool_pages", type=int, default=0,
                       help="serve --paged: per-layer pool size in pages "
                            "(0 = slab parity; smaller = the HBM win, "
                            "admission defers under pool pressure)")
        p.add_argument("--kv_dtype", choices=["float32", "int8"],
                       default=None,
                       help="serve: KV page storage dtype. int8 stores "
                            "pages quantized (absmax per page x kv-head) "
                            "with per-page fp32 scales — ~4x fewer pool "
                            "bytes, bounded-divergence numerics. Implies "
                            "--paged.")
        p.add_argument("--no_prefix_cache", action="store_true",
                       help="serve --paged: disable the radix prefix index "
                            "(pages still pooled, no cross-request sharing)")
        p.add_argument("--host_tier_bytes", type=int, default=0,
                       help="serve --paged: host-memory KV tier capacity in "
                            "bytes (cold prefix pages spill there instead "
                            "of dropping; restored checksum-verified on "
                            "hit). 0 = auto (2x the device pool); disable "
                            "with --no_host_tier")
        p.add_argument("--no_host_tier", action="store_true",
                       help="serve --paged: disable the host-memory KV tier "
                            "(pool pressure drops cold pages again)")
        p.add_argument("--shared_prefix_len", type=int, default=0,
                       help="serve: prepend one common random prefix of this "
                            "many tokens to every trace prompt (the "
                            "prefix-cache workload shape)")
        p.add_argument("--prefix_families", type=int, default=1,
                       help="serve: rotate through this many DISTINCT "
                            "shared prefixes in runs of four requests — "
                            "the idle family's prefix goes cold under pool "
                            "pressure (the host-tier spill/restore "
                            "workload shape)")
        p.add_argument("--ttft_deadline_ms", type=float, default=None,
                       help="serve: per-request first-token deadline "
                            "(relative to arrival; converted to the virtual "
                            "block clock at --block_time_ms per block)")
        p.add_argument("--deadline_ms", type=float, default=None,
                       help="serve: per-request completion deadline — a "
                            "stream past it retires with a partial "
                            "expired=True completion")
        p.add_argument("--block_time_ms", type=float, default=1.0,
                       help="serve: ms of deadline budget one decode block "
                            "consumes (set to the measured per-block time "
                            "on hardware; default 1.0 = ms == blocks)")
        p.add_argument("--max_queue", type=int, default=None,
                       help="serve: bound the arrived admission backlog — "
                            "overflow is load-shed with a structured "
                            "Rejected(retry_after) instead of queueing "
                            "unboundedly")
        p.add_argument("--shed_policy", choices=["tail", "deadline"],
                       default="tail",
                       help="serve: overflow victim policy (tail = newest "
                            "arrival, deadline = laxest deadline)")
        p.add_argument("--snapshot_path", type=str, default=None,
                       help="serve: crash-recovery snapshot file — written "
                            "atomically every few blocks, removed on clean "
                            "drain; if it EXISTS at startup the previous "
                            "run's in-flight streams are restored and "
                            "finished bit-identical")
        p.add_argument("--park-idle-blocks", "--park_idle_blocks",
                       dest="park_idle_blocks", type=int, default=0,
                       help="serve: park a conversation whose stream has "
                            "been idle (no decode progress) for this many "
                            "blocks — its KV pages and engine state move "
                            "to the durable tier at --park-dir and it "
                            "vacates device AND host entirely; resume via "
                            "submit(resume=...) continues bit-identical "
                            "without re-prefill. 0 = explicit park() only")
        p.add_argument("--park-dir", "--park_dir",
                       dest="park_dir", type=str, default=None,
                       help="serve: directory for the durable conversation "
                            "tier (crash-consistent per-conversation "
                            "manifests; torn writes from a SIGKILL are "
                            "quarantined on the next open, never served). "
                            "Required when --park-idle-blocks > 0")
        p.add_argument("--replicas", type=int, default=1,
                       help="serve: N>1 drives N ServeEngine replicas "
                            "behind the Router front door (prefix-affinity "
                            "placement, per-tenant WFQ, heartbeat failover, "
                            "graceful drain) over one shared model")
        p.add_argument("--disagg", action="store_true",
                       help="serve --replicas N --paged: prefill/decode "
                            "disaggregation — the first --prefill_replicas "
                            "workers run prefill only and hand finished KV "
                            "pages to the decode workers through "
                            "checksummed handoffs (decode ITL with zero "
                            "prefill sharing; streams bit-identical to a "
                            "single engine)")
        p.add_argument("--prefill_replicas", type=int, default=1,
                       help="serve --disagg: how many of the N replicas "
                            "are dedicated prefill workers (the rest run "
                            "the fused decode scan + page adoption)")
        p.add_argument("--autoscale", action="store_true",
                       help="serve: run the SLO-driven autoscaler in the "
                            "router block loop — the fleet starts at "
                            "--min_replicas and scales between the min/max "
                            "bounds (scale-up on weighted backlog / pool "
                            "pressure / SLO burn, scale-down drains + "
                            "parks the least-loaded replica; warm re-spawn "
                            "from parked snapshots). With --disagg the "
                            "prefill and decode pools scale independently "
                            "(bounds apply per pool)")
        p.add_argument("--min_replicas", type=int, default=1,
                       help="serve --autoscale: fleet floor (crashes below "
                            "it are re-spawned immediately)")
        p.add_argument("--max_replicas", type=int, default=0,
                       help="serve --autoscale: fleet ceiling (0 = "
                            "max(--replicas, --min_replicas + 1))")
        p.add_argument("--scale_slo_ms", type=float, default=None,
                       help="serve --autoscale: arm a TTFT SLO objective "
                            "at this many wall ms on every replica — its "
                            "multi-window burn alerts become the "
                            "autoscaler's slo_burn scale-up signal")
        p.add_argument("--scale_up_backlog", type=float, default=1.0,
                       help="serve --autoscale: weighted router backlog "
                            "(in blocks of work per live replica) above "
                            "which the fleet scales up")
        p.add_argument("--scale_patience_blocks", type=int, default=2,
                       help="serve --autoscale: consecutive over-threshold "
                            "blocks before a scale-up fires")
        p.add_argument("--scale_down_util", type=float, default=0.4,
                       help="serve --autoscale: fleet utilization below "
                            "which the pool is oversized")
        p.add_argument("--scale_down_idle_blocks", type=int, default=8,
                       help="serve --autoscale: consecutive low-util "
                            "blocks before a scale-down drains a replica")
        p.add_argument("--scale_cooldown_blocks", type=int, default=8,
                       help="serve --autoscale: minimum blocks between "
                            "scale events of one pool")
        p.add_argument("--diurnal", type=float, default=0.0,
                       help="serve: diurnal arrival-rate amplitude in "
                            "[0,1) — rate scaled by 1 + a*sin(2*pi*t/"
                            "--diurnal_period_blocks) (the autoscaling "
                            "workload shape)")
        p.add_argument("--diurnal_period_blocks", type=int, default=64,
                       help="serve --diurnal: day length in blocks")
        p.add_argument("--burst_every", type=int, default=0,
                       help="serve: every this many blocks, the first "
                            "quarter of the window arrives --burst_mult x "
                            "faster (square-wave flash crowds)")
        p.add_argument("--burst_mult", type=float, default=4.0,
                       help="serve --burst_every: burst rate multiplier")
        p.add_argument("--tenants", type=int, default=0,
                       help="serve: label trace requests with this many "
                            "tenants, Zipf-skewed (t0 is the heavy hitter); "
                            "the report grows a per-tenant table")
        p.add_argument("--tenant_skew", type=float, default=1.0,
                       help="serve --tenants: Zipf exponent of the tenant "
                            "distribution (0 = uniform)")
        p.add_argument("--adapters", type=int, default=0,
                       help="serve: N>0 registers N demo LoRA adapters and "
                            "labels trace requests with Zipf-skewed "
                            "adapter names — per-request fine-tunes served "
                            "from ONE base model via the device-resident "
                            "adapter pool (S-LoRA batching)")
        p.add_argument("--adapter_rank", type=int, default=8,
                       help="serve --adapters: LoRA rank r of the demo "
                            "adapters (= the pool's padded max rank)")
        p.add_argument("--adapter_pool_slots", type=int, default=0,
                       help="serve --adapters: device-resident pool slots "
                            "incl. the identity slot (0 = adapters+1, i.e. "
                            "no churn; smaller forces LRU load/evict churn)")
        p.add_argument("--adapter_skew", type=float, default=1.0,
                       help="serve --adapters: Zipf exponent of adapter "
                            "popularity (a0 the heavy hitter; 0 = uniform)")
        p.add_argument("--grammar_frac", type=float, default=0.0,
                       help="serve: label this fraction of trace requests "
                            "with demo grammars (regex + JSON-schema, "
                            "cycled) — structured decoding enforced inside "
                            "the fused scan as a per-slot token-DFA mask; "
                            "constrained output always parses")
        p.add_argument("--grammars", type=int, default=3,
                       help="serve --grammar_frac: how many demo grammars "
                            "to register (g0..gN-1, cycling the demo menu)")
        p.add_argument("--grammar_pool_slots", type=int, default=0,
                       help="serve --grammar_frac: device-resident grammar "
                            "pool slots incl. the identity slot (0 = "
                            "grammars+1, i.e. no churn; smaller forces LRU "
                            "load/evict churn of the mask tables)")
        p.add_argument("--grammar_states", type=int, default=96,
                       help="serve --grammar_frac: padded DFA-state "
                            "capacity per pool slot (mask table is "
                            "states x vocab per slot)")
        p.add_argument("--crash_replica_at", type=int, default=None,
                       help="serve --replicas: crash the last replica at "
                            "this router block — its streams fail over to "
                            "the survivors bit-identical (the CI smoke "
                            "asserts the report's failover counters)")
        p.add_argument("--trace_out", type=str, default=None,
                       help="serve: write the engine's per-request timeline "
                            "(Chrome trace-event JSON, loadable in "
                            "Perfetto) to this path after the run; also "
                            "turns structured tracing on")
        p.add_argument("--metrics_out", type=str, default=None,
                       help="serve: write the engine's metrics registry "
                            "(Prometheus text exposition; a .json path "
                            "writes the JSON snapshot) to this path after "
                            "the run")
        p.add_argument("--incident_dir", type=str, default=None,
                       help="serve: arm the incident flight recorder — "
                            "deadline-miss bursts, pool-exhaustion storms, "
                            "page corruption, dispatch fail-stop and "
                            "replica crashes dump bounded schema-validated "
                            "evidence bundles (trace slice + metrics "
                            "snapshot + engine state) into this directory; "
                            "implies tracing on")
        p.add_argument("--slo_ttft_ms", type=float, default=None,
                       help="serve: TTFT SLO objective in wall ms — "
                            "evaluated with multi-window burn rates each "
                            "block; alerts land on the trace and in "
                            "serve_slo_alerts_total, status in the report")
        p.add_argument("--slo_itl_ms", type=float, default=None,
                       help="serve: inter-token latency SLO objective in "
                            "wall ms (see --slo_ttft_ms)")
        p.add_argument("--slo_target", type=float, default=0.95,
                       help="serve: required good fraction for the SLO "
                            "objectives (error budget = 1 - target)")
        p.add_argument("--fault_plan", type=str, default=None,
                       help="serve: seeded chaos plan (JSON object or path "
                            "to one): pool_exhaust_prob/pool_storm_len/"
                            "dispatch_fail_prob/dispatch_max_failures/"
                            "corrupt_page_prob/seed")
        p.add_argument("--quantize", action="store_true",
                       help="serve int8 weight-only quantized params")
        p.add_argument("--model", choices=["llama", "mixtral", "dbrx", "olmoe"],
                       default="llama")
    args = parser.parse_args(argv)
    if args.tiny:
        from common import force_cpu_mesh

        force_cpu_mesh()
    else:
        from neuronx_distributed_tpu.utils.compile_cache import (
            place_compile_cache,
        )

        place_compile_cache()
    {"generate": cmd_generate, "benchmark": cmd_benchmark,
     "check-accuracy": cmd_check_accuracy, "serve": cmd_serve}[args.cmd](args)


if __name__ == "__main__":
    main()
