"""Shared example-script machinery (reference
``examples/training/llama/training_utils.py`` — argparse plumbing, synthetic
data, Throughput/metrics logging — and the checkpoint-resume flow of
``run_llama_nxd.py:205-237``).

Every training script in this directory follows the same skeleton:
``neuronx_distributed_config`` → ``initialize_parallel_model`` →
``initialize_parallel_optimizer`` → ``make_train_step`` → :func:`train_loop`.
Scripts accept ``--tiny`` so CI can smoke them on the virtual CPU mesh
(SURVEY §4.2: recreate the reference's single-host multi-rank tier with a
forced-device-count CPU mesh).
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np

from neuronx_distributed_tpu.checkpoint import (
    finalize_checkpoint,
    has_checkpoint,
    load_checkpoint,
    save_checkpoint,
)
from neuronx_distributed_tpu.utils import MetricsWriter, Throughput, get_logger
from neuronx_distributed_tpu.utils.profiler import profile_steps, step_annotation

logger = get_logger("nxd.examples")


def force_cpu_mesh(n_devices: int = 8, check: bool = True) -> None:
    """Self-provision a virtual CPU device mesh for ``--tiny`` runs (same
    pattern as ``__graft_entry__.dryrun_multichip``): ``--tiny`` is the CPU
    smoke wherever it runs, so the platform is pinned through jax.config —
    on a machine with a chip the environment would otherwise pick the TPU.

    ``check=False`` skips the device-count probe, which initializes the XLA
    backend — required when ``jax.distributed.initialize`` (setup_distributed)
    still has to run, since that must precede any backend use."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    jax.config.update("jax_platforms", "cpu")
    if check and len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"virtual CPU mesh has {len(jax.devices())} devices (< {n_devices}); "
            "jax was already initialized on another platform — set "
            f"JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count={n_devices} "
            "before python starts"
        )


def add_common_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    parser.add_argument("--tensor_parallel_size", "--tp", type=int, default=None)
    parser.add_argument("--pipeline_parallel_size", "--pp", type=int, default=None)
    # pod launch trio (reference torchrun --master_addr/--nnodes/--node_rank);
    # the NXD_* env vars work too — see scripts/launch_pod.sh
    parser.add_argument("--coordinator_address", type=str, default=None,
                        help="host0:port of the pod coordinator (multi-host)")
    parser.add_argument("--num_processes", type=int, default=None)
    parser.add_argument("--process_id", type=int, default=None)
    parser.add_argument("--batch_size", type=int, default=None)
    parser.add_argument("--seq_len", type=int, default=None)
    parser.add_argument("--steps", type=int, default=None)
    parser.add_argument("--warmup_steps", type=int, default=0)
    parser.add_argument("--grad_accum_usteps", type=int, default=1,
                        help="microbatch accumulation inside the jitted step "
                             "(reference run_llama_nxd_ptl.py:171)")
    parser.add_argument("--lr", type=float, default=1e-4)
    parser.add_argument("--weight_decay", type=float, default=0.01)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--log_every", type=int, default=10)
    parser.add_argument("--checkpoint_dir", type=str, default=None)
    parser.add_argument("--checkpoint_every", type=int, default=0,
                        help="save every N steps (0 = only at end when dir set)")
    parser.add_argument("--metrics_file", type=str, default=None)
    parser.add_argument("--profile_dir", type=str, default=None,
                        help="jax.profiler XProf trace output dir")
    parser.add_argument("--trace_out", type=str, default=None,
                        help="write a Chrome/Perfetto trace-event JSON of "
                             "the host-side step timeline (step spans, "
                             "checkpoint saves) to this path")
    parser.add_argument("--metrics_out", type=str, default=None,
                        help="write the run's metrics registry here "
                             "(Prometheus text exposition; .json suffix "
                             "writes the JSON snapshot instead)")
    parser.add_argument(
        "--tiny", action="store_true",
        help="shrink the model/batch to CI scale (virtual CPU mesh smoke)",
    )
    return parser


def make_lr(args, steps: int):
    """LR for the examples: constant when --warmup_steps is 0, else linear
    warmup -> cosine decay to 10% (the reference's CosineAnnealing-with-
    warmup, examples/training/llama/lr.py, wired via --warmup_steps). The
    returned optax schedule passes straight through
    ``initialize_parallel_optimizer(learning_rate=...)``."""
    if not getattr(args, "warmup_steps", 0):
        return args.lr
    import optax

    return optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=args.lr,
        warmup_steps=args.warmup_steps, decay_steps=max(steps, args.warmup_steps + 1),
        end_value=args.lr * 0.1)


def setup_distributed(args) -> bool:
    """Join the pod runtime when the launch trio is present (call before any
    mesh/model init). Returns True on a multi-process run. Safe to call
    unconditionally — single-host runs are a no-op, mirroring how every
    reference example unconditionally does ``init_process_group``."""
    from neuronx_distributed_tpu.parallel.distributed import initialize_distributed

    multi = initialize_distributed(
        coordinator_address=getattr(args, "coordinator_address", None),
        num_processes=getattr(args, "num_processes", None),
        process_id=getattr(args, "process_id", None),
    )
    if multi:
        logger.info("pod process %d/%d (%d local devices)",
                    jax.process_index(), jax.process_count(),
                    jax.local_device_count())
    return multi


def setup_example(args, n_devices: int = 8) -> bool:
    """Standard example bootstrap, in the one order that works: platform
    switch for ``--tiny`` (no backend probe), THEN the pod join —
    ``jax.distributed.initialize`` must precede any backend use — then the
    device-count sanity check. Returns True on a multi-process run."""
    if getattr(args, "tiny", False):
        force_cpu_mesh(n_devices, check=False)
    else:
        # real-size programs take minutes to compile; the tiny smoke's stay
        # under the cache's one-second floor and would store nothing
        from neuronx_distributed_tpu.utils.compile_cache import (
            place_compile_cache,
        )

        place_compile_cache()
    multi = setup_distributed(args)
    if getattr(args, "tiny", False) and len(jax.local_devices()) < 2:
        raise SystemExit(
            "tiny smoke needs a multi-device CPU mesh; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices}")
    return multi


def distribute_batches(batches: Iterator[Dict[str, np.ndarray]],
                       global_batch: int) -> Iterator[Dict[str, np.ndarray]]:
    """Make a synthetic GLOBAL-batch iterator pod-correct: on a multi-process
    run each host keeps only its row slice (identical global generation from
    the shared seed); single-process this is a passthrough."""
    if jax.process_count() == 1:
        return batches
    return host_local_batches(batches, global_batch)


def host_local_batches(batches: Iterator[Dict[str, np.ndarray]],
                       global_batch: int) -> Iterator[Dict[str, np.ndarray]]:
    """Slice a GLOBAL-batch iterator down to this host's rows (processes
    generate identical global batches from the shared seed, then each keeps
    its slice — train_loop reassembles via shard_host_batch). Real corpora
    skip this: TokenShardDataset shards at the source via rank/world_size."""
    from neuronx_distributed_tpu.parallel.distributed import host_batch_slice

    sl = host_batch_slice(global_batch)
    for b in batches:
        yield {k: v[sl] for k, v in b.items()}


def synthetic_lm_batches(vocab_size: int, batch: int, seq: int,
                         seed: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    """Deterministic synthetic next-token batches (the reference examples read
    tokenized HDF5/arrow shards; data loading is orthogonal to what these
    scripts exercise, so synthetic keeps them hermetic)."""
    rs = np.random.RandomState(seed)
    while True:
        ids = rs.randint(0, vocab_size, (batch, seq + 1), dtype=np.int64)
        yield {"ids": ids[:, :-1].astype(np.int32), "labels": ids[:, 1:].astype(np.int32)}


def synthetic_mlm_batches(vocab_size: int, batch: int, seq: int, seed: int = 0,
                          mask_token: int = 103, mask_prob: float = 0.15,
                          ignore_index: int = -100) -> Iterator[Dict[str, np.ndarray]]:
    """BERT-style MLM+NSP batches (the reference's HDF5 records carry
    input_ids / segment_ids / input_mask / masked_lm_labels /
    next_sentence_labels — same five fields here)."""
    rs = np.random.RandomState(seed)
    while True:
        ids = rs.randint(5, vocab_size, (batch, seq), dtype=np.int64)
        seg = (np.arange(seq)[None, :] >= rs.randint(1, seq, (batch, 1))).astype(np.int32)
        mask = np.ones((batch, seq), np.int32)
        pad_from = rs.randint(seq // 2, seq + 1, (batch,))
        for i, p in enumerate(pad_from):
            mask[i, p:] = 0
        mlm_labels = np.full((batch, seq), ignore_index, np.int64)
        masked = (rs.rand(batch, seq) < mask_prob) & (mask == 1)
        mlm_labels[masked] = ids[masked]
        input_ids = ids.copy()
        input_ids[masked] = mask_token
        nsp = rs.randint(0, 2, (batch,), dtype=np.int64)
        yield {
            "input_ids": input_ids.astype(np.int32),
            "token_type_ids": seg,
            "attention_mask": mask,
            "masked_lm_labels": mlm_labels.astype(np.int32),
            "next_sentence_labels": nsp.astype(np.int32),
        }


def train_loop(
    step_fn: Callable,
    state,
    batches: Iterator[Dict[str, np.ndarray]],
    steps: int,
    *,
    batch_size: int,
    log_every: int = 10,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    metrics_file: Optional[str] = None,
    profile_dir: Optional[str] = None,
    seed: int = 0,
    extra_metrics: Optional[Dict[str, Any]] = None,
    trace_out: Optional[str] = None,
    metrics_out: Optional[str] = None,
):
    """Run ``steps`` training steps with throughput logging, optional
    periodic checkpointing, and optional XProf profiling. Returns
    ``(final_state, last_metrics_dict)``. ``extra_metrics``: static
    key/values (e.g. data-loader stats) attached to every metrics line.

    ``trace_out``/``metrics_out`` arm the host-side observability layer
    (``neuronx_distributed_tpu.observability``): the trainer lane carries
    one span per step (the dispatch+sync wall time) and per checkpoint
    save, exported as Perfetto-loadable Chrome trace JSON; the registry
    records the step-time histogram, tokens/s gauge and checkpoint
    durations, exported as Prometheus text (or a JSON snapshot for a
    ``.json`` path). Both default off — the step loop then pays one boolean
    check per step."""
    from neuronx_distributed_tpu.observability import MetricsRegistry, Tracer

    start_step = int(state.step)
    throughput = Throughput(batch_size)
    writer = MetricsWriter(metrics_file)
    tracer = Tracer(enabled=bool(trace_out))
    registry = MetricsRegistry()
    m_step = registry.histogram("train_step_ms",
                                help="per-step dispatch+sync wall ms")
    m_ckpt = registry.histogram("train_checkpoint_ms",
                                help="checkpoint save-call wall ms")
    m_tok = registry.gauge("train_tokens_per_sec",
                           help="tokens/s over the logging window")
    m_steps = registry.counter("train_steps", help="optimizer steps run")

    def timed_save(tag_step: int, **kw) -> None:
        t0 = time.perf_counter()
        with tracer.span(f"checkpoint_{tag_step}", ("trainer", "checkpoint")):
            save_checkpoint(checkpoint_dir, f"step_{tag_step}", state,
                            user_content={"step": tag_step}, num_kept=3, **kw)
        m_ckpt.observe((time.perf_counter() - t0) * 1e3)

    metrics = {}
    last_logged = start_step
    # Multi-host: each process's iterator yields its LOCAL rows; assemble the
    # global DP-sharded batch before the step (reference DistributedSampler +
    # DDP input scatter role). Single-host the raw numpy feeds jit directly
    # ON PURPOSE: make_array_from_process_local_data requires the batch to
    # divide evenly over the DP axes, while jit on raw numpy tolerates uneven
    # shardings (GSPMD pads) — single-host keeps the laxer contract.
    if jax.process_count() > 1:
        from neuronx_distributed_tpu.parallel.distributed import shard_host_batch
    else:
        shard_host_batch = lambda b: b  # noqa: E731
    try:
        with profile_steps(profile_dir):
            for i in range(start_step, steps):
                batch = shard_host_batch(next(batches))
                t0 = time.perf_counter()
                with step_annotation(i):
                    state, metrics = step_fn(state, batch, jax.random.key(seed + i + 1))
                t1 = time.perf_counter()
                # host wall per loop iteration: dispatch plus whatever
                # backpressure sync the runtime imposes (steady-state this
                # converges to true step time; the synced number is the
                # throughput window below)
                m_step.observe((t1 - t0) * 1e3)
                m_steps.inc()
                if tracer.enabled:
                    tracer.complete(f"step_{i}", ("trainer", "steps"), t0, t1,
                                    args={"step": i + 1})
                if log_every and ((i + 1) % log_every == 0 or i + 1 == steps):
                    loss = float(metrics["loss"])  # host fetch = step synced
                    # get_throughput()'s time delta spans the steps since the
                    # previous log call — scale by exactly that count
                    seq_s = throughput.get_throughput() * (i + 1 - last_logged)
                    last_logged = i + 1
                    seq_len = next(
                        (v.shape[1] for v in batch.values()
                         if getattr(v, "ndim", 0) >= 2), 1)
                    m_tok.set(round(seq_s * seq_len, 1))
                    logger.info("step %d/%d loss %.4f (%.2f seq/s)", i + 1, steps, loss, seq_s)
                    writer.log(i + 1, loss=loss, seqs_per_sec=seq_s,
                               grad_norm=metrics.get("grad_norm", 0.0),
                               **(extra_metrics or {}))
                if checkpoint_dir and checkpoint_every and (i + 1) % checkpoint_every == 0:
                    timed_save(i + 1, async_save=True)
        if checkpoint_dir:
            timed_save(steps)
    finally:
        finalize_checkpoint()
        writer.close()
        if trace_out:
            tracer.export_chrome(trace_out)
        if metrics_out:
            registry.dump(metrics_out)
    return state, metrics


def maybe_resume(checkpoint_dir: Optional[str], state):
    """Resume from the newest completed tag when one exists (reference
    ``latest_if_exists``, run_llama_nxd.py:205-237)."""
    if not checkpoint_dir or not has_checkpoint(checkpoint_dir):
        return state
    target = jax.tree.map(lambda x: x, state)
    restored, content = load_checkpoint(checkpoint_dir, target=target)
    logger.info("resumed from %s at step %s", checkpoint_dir, (content or {}).get("step"))
    return restored
