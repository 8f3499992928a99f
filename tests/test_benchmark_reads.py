"""What the chip benchmark reads FROM THE PROGRAM is still produced by it.

``benchmark/drivers/*.py``, ``benchmark/layer_metrics/*.py``,
``benchmark/trace_parts.py`` and ``benchmark/trace_reduce.py`` read the
program by name: ``engine.stats`` keys, the paged pool's counters,
``Completion`` fields, engine and ``CausalLM`` attributes, the names of the
three jitted functions a device trace is reduced by, the engine's own spans
of a round and their args (``benchmark/phase_spans.py``). A rename fails
silently there: ``_StatsView`` defaults a missing key to 0, a renamed
jitted function zeroes ``decode.step_ms`` / ``prefill.ms_per_call`` only
under trace on the chip, and the one CPU rehearsal of the serving driver
(``tests/benchmark/test_bm_files.py``) sees neither. Here every such name is
one case, asserted PRODUCED (present and non-trivial) by one tiny paged,
prefix-cached, fused ``ServeEngine`` run, one tiny OLMoE run for the routing
counters and one lowered train step. The table lives in this file;
``test_every_name_is_still_read`` keeps it from outliving what it guards.
"""

import re
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import CausalLM, ServeEngine
from neuronx_distributed_tpu.inference.engine import Rejected
from neuronx_distributed_tpu.models.granite_hybrid import (
    GraniteHybridConfig,
    GraniteHybridForCausalLM,
)
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.models.olmoe import OlmoeConfig, OlmoeForCausalLM
from neuronx_distributed_tpu.trainer import (
    create_train_state,
    initialize_parallel_model,
    initialize_parallel_optimizer,
    make_train_step,
    neuronx_distributed_config,
)
from tests import tiny

BENCHMARK = Path(__file__).resolve().parent.parent / "benchmark"
TINY = dict(vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64, dtype=jnp.float32,
            use_flash_attention=False, remat_policy=None)
BUDGET, BLOCK_STEPS = 6, 4


def _serve(cfg, model_cls, prompts, prefix_cache=True):
    """The serving driver's calls, at toy size: ``CausalLM`` paged with the
    prefix cache on (off for a model with per-slot state, as its configuration
    file says), ``compile()``, a traced fused engine, ``submit`` /
    ``step_block`` until everything drained."""
    params = tiny.make_params(model_cls, cfg, seed=0)
    lm = CausalLM(cfg, params, model_cls, buckets=(16, 32), max_batch=2, page_size=4,
                  prefix_cache=prefix_cache)
    lm.compile()
    engine = ServeEngine(lm, block_steps=BLOCK_STEPS, rng=jax.random.key(0), trace=True)
    ids = [engine.submit(p, max_new_tokens=BUDGET, arrival_block=engine.blocks)
           for p in prompts]
    while engine.step_block():
        pass
    return types.SimpleNamespace(lm=lm, engine=engine, ids=ids)


@pytest.fixture(scope="module")
def run():
    rng = np.random.RandomState(0)
    head = rng.randint(1, 128, (8,)).astype(np.int32)
    # the last two share their first eight tokens (two whole pages) with the
    # first: the prefix cache has something to hit
    prompts = [np.concatenate([head, rng.randint(1, 128, (n,)).astype(np.int32)])
               for n in (4, 12, 5, 6)]
    got = _serve(LlamaConfig(**TINY), LlamaForCausalLM, prompts)
    got.completion = got.engine.completed[0]
    got.fused = got.lm.compile_session_decode_fused(
        got.engine.block_steps, got.engine.slot_sampler, got.engine.pad_token_id)
    got.insert = got.lm._paged_insert_programs(1, 16)
    # an engine that sheds, over the same programs: what submit() hands the
    # driver in place of an id
    shedding = ServeEngine(got.lm, block_steps=BLOCK_STEPS, max_queue=0, rng=jax.random.key(0))
    got.shed = [shedding.submit(p, max_new_tokens=2) for p in prompts]
    got.shedding = shedding
    return got


@pytest.fixture(scope="module")
def moe_run():
    cfg = OlmoeConfig(**dict(TINY, num_kv_heads=4, intermediate_size=32, num_experts=8, top_k=2))
    rng = np.random.RandomState(1)
    return _serve(cfg, OlmoeForCausalLM,
                  [rng.randint(1, 128, (n,)).astype(np.int32) for n in (6, 9)])


@pytest.fixture(scope="module")
def hybrid_run():
    """One period of Granite-4.0-H's stack, tiny: prompts of 6 and 9 tokens in
    buckets of 16, a scan chunk of 8."""
    cfg = GraniteHybridConfig(**dict(
        TINY, num_layers=10, layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
        head_dim=8, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8,
        attention_multiplier=0.125, param_dtype=jnp.float32))
    rng = np.random.RandomState(2)
    got = _serve(cfg, GraniteHybridForCausalLM,
                 [rng.randint(1, 128, (n,)).astype(np.int32) for n in (6, 9)],
                 prefix_cache=False)
    got.fused = got.lm.compile_session_decode_fused(
        got.engine.block_steps, got.engine.slot_sampler, got.engine.pad_token_id)
    got.insert = got.lm._paged_insert_programs(1, 16)
    return got


@pytest.fixture(scope="module")
def train_step_text():
    cfg = LlamaConfig(**dict(TINY, max_seq_len=16))
    nxd = neuronx_distributed_config(tensor_parallel_size=1)
    ids = np.random.RandomState(0).randint(1, 128, (2, 16)).astype(np.int32)
    model = initialize_parallel_model(nxd, lambda: LlamaForCausalLM(cfg), jnp.asarray(ids))
    opt = initialize_parallel_optimizer(nxd, model, learning_rate=1e-3, weight_decay=0.0)
    state = create_train_state(model, opt)

    def loss_fn(p, batch, rng):
        return model.module.apply({"params": p}, batch["ids"], batch["labels"],
                                  method=LlamaForCausalLM.loss)

    step = make_train_step(model, opt, loss_fn)
    return step.lower(state, {"ids": ids, "labels": ids}, jax.random.key(0)).as_text()


def _positive_stat(key):
    return lambda r: r.engine.stats[key] > 0


def _dispatch_spans(r):
    """The spans ``engine.host_ms_per_block`` subtracts: complete events of
    the tracer's dispatch lane, with a start and a duration."""
    spans = [e for e in r.engine.tracer.events() if e["ph"] == "X" and e["lane"][1] == "dispatch"]
    return spans and all(e["dur"] >= 0 and "ts" in e for e in spans)


def _stamps(r):
    """One stamp per token, non-decreasing, for every completion."""
    return all(c.token_ts is not None and len(c.token_ts) == len(c.tokens)
               and bool(np.all(np.diff(np.asarray(c.token_ts)) >= 0))
               for c in r.engine.completed)


# (the name as the benchmark's sources spell it, what "produced" means)
SERVING_READS = [
    ('stats["blocks"]', _positive_stat("blocks")),
    ('stats["decode_blocks"]', _positive_stat("decode_blocks")),
    ('stats["inserts"]', _positive_stat("inserts")),
    ('stats["inserted_requests"]', lambda r: r.engine.stats["inserted_requests"] == len(r.ids)),
    ('stats["generated_tokens"]',
     lambda r: r.engine.stats["generated_tokens"] == BUDGET * len(r.ids)),
    ('stats["pages_in_use_peak"]',
     lambda r: 0 < r.engine.session.paged.stats["pages_in_use_peak"]
     <= r.lm.config.page_pool_pages),
    ('stats["prefix_hits"]', lambda r: r.engine.session.paged.stats["prefix_hits"] > 0),
    ("engine.stats.items()",
     lambda r: {"blocks", "generated_tokens"} <= {k for k, _ in r.engine.stats.items()}),
    ("c.tokens", lambda r: all(len(c.tokens) == BUDGET for c in r.engine.completed)),
    ("c.token_ts", _stamps),
    ("c.request_id", lambda r: sorted(c.request_id for c in r.engine.completed) == sorted(r.ids)),
    ("c.finish_reason", lambda r: isinstance(r.completion.finish_reason, str)
     and r.completion.finish_reason != ""),
    ("c.expired", lambda r: r.completion.expired is False),
    ("c.cancelled", lambda r: r.completion.cancelled is False),
    ("engine.completed", lambda r: len(r.engine.completed) == len(r.ids)),
    ("engine.rejected", lambda r: r.engine.rejected == [] and len(r.shedding.rejected) > 0),
    ("'reason'", lambda r: any(isinstance(s, Rejected) and s.reason for s in r.shed)),
    ("engine.blocks", lambda r: r.engine.blocks == r.engine.stats["blocks"] > 0),
    ("engine.block_steps", lambda r: r.engine.block_steps == BLOCK_STEPS),
    ("engine.tracer.events()", _dispatch_spans),
    ("engine.session.paged", lambda r: r.engine.session.paged is not None),
    ("engine.slot_sampler", lambda r: r.engine.slot_sampler is not None),
    ("engine.pad_token_id", lambda r: isinstance(r.engine.pad_token_id, int)),
    ("lm.compile_ms", lambda r: len(r.lm.compile_ms) >= 2
     and all(ms > 0 for ms in r.lm.compile_ms.values())),
    ("lm.buckets", lambda r: tuple(r.lm.buckets) == (16, 32)),
    ("lm.max_batch", lambda r: r.lm.max_batch == 2),
    ("lm._bucket_for", lambda r: (r.lm._bucket_for(9), r.lm._bucket_for(17)) == (16, 32)),
    ("lm.config.page_pool_pages", lambda r: r.lm.config.page_pool_pages > 0),
    ('lm.kv_cache_bytes()["kv_bytes"]', lambda r: r.lm.kv_cache_bytes()["kv_bytes"] > 0),
    ("memory_analysis()", lambda r: all(
        getattr(r.fused.memory_analysis(), f) >= 0
        for f in ("temp_size_in_bytes", "argument_size_in_bytes", "output_size_in_bytes"))),
    ("jit_fused_fn", lambda r: "jit_fused_fn" in r.fused.as_text().split("\n", 1)[0]),
    ("jit_insert_fn", lambda r: "jit_insert_fn" in r.insert.as_text().split("\n", 1)[0]),
]
MOE_READS = ["moe_experts_touched", "moe_assignments", "moe_layer_steps"]
# the paged insert's side of the same counter (PR 29): in `engine.stats`, so
# in every record's `engine_stats`; no metric of BENCHMARK.json reads them yet
# ... and the rows the grouped kernel's dots ran over (PR 43), and the passes
# the layer calls made over their sorted lists (PR 51: one a call where every
# expert is held; ``moe.insert_real_row_share`` reads assignments / rows)
MOE_INSERT_STATS = ["moe_insert_experts_touched", "moe_insert_assignments",
                    "moe_insert_layer_calls", "moe_insert_rows",
                    "moe_insert_rows_multiplied", "moe_insert_passes"]
# the insert's twins of program_calls / host_fetches (PR 32): in every record's
# `engine_stats` the same way; (calls + fetches) / inserts reads 2.0 where every
# admission was one program and one fetch
INSERT_HOST_OPS = ["insert_program_calls", "insert_host_fetches"]
# every pick of the live rows' routers (PR 37): equal to `moe_assignments` where
# every expert is held; more where the layer holds a share (tests/test_deepseek_v2.py)
ROUTED_READS = ["moe_assignments_routed"]
# how far the fused decode blocks read the cache (PR 38): in `engine.stats`, so
# in every record's `engine_stats`; no metric of BENCHMARK.json reads them yet
# ... and over how many rows (PR 40): the rung of rows that holds the live ones
WALK_STATS = ["kv_walk_tokens", "kv_walk_steps", "kv_walk_row_slots"]
# a model with per-slot state beside its pages (PR 44): what its inserts'
# chunked scan ran over (``ssm.scan_real_token_share`` divides them) ...
SSM_READS = ["ssm_scan_tokens", "ssm_scan_positions"]
# ... and the names its mixer's regions carry in a device trace (the flax
# module ``mamba``, ``jax.named_scope``s inside it, ``state_rows`` around the
# insert's gather and scatter of slot rows); ``scope_parts.json`` is the
# benchmark's and has no rows for them yet. True: in the fused decode's step
SSM_SCOPES = {"mamba": None, "ssm_in_proj": None, "ssm_conv": None, "ssm_gate_norm": None,
              "ssm_out_proj": None, "ssm_scan": False, "state_rows": False, "ssm_step": True}
TRAIN_READS = ["jit_step_fn"]
# what the process's start-up spent compiling, by part (PR 54): the compile
# log's totals (``utils/compile_cache.py``), mirrored into ``engine.stats`` when
# the engine is built; one a ``setup.*`` reader
STARTUP_READS = ["startup_trace_lower_ms", "startup_xla_compile_ms", "startup_cache_load_ms",
                 "startup_format_ms", "startup_cache_misses", "startup_unnamed_ms",
                 "startup_init_ms"]
# the engine's own spans of a round (PR 39), as ``benchmark/phase_spans.py`` and
# the seven readers over it spell them: (name, lane's track) of a complete span
PHASE_SPANS = [('"step_block"', "phases"), ('"admit"', "phases"), ('"admission"', "phases"),
               ('"observe"', "phases"), ('"launch"', "phases"), ('"harvest"', "phases"),
               ('"cache_plan"', "pool"), ('"cache_commit"', "pool"), ('"queued"', None),
               ('"fetch"', "dispatch"), ('"insert_fetch"', "dispatch"),
               ('"decode_block"', "blocks")]
# the args of those spans that a reader takes: (arg, the span that carries it)
PHASE_ARGS_READ = [('"worked"', "step_block"), ('"decoding"', "admission")]
# ... and the ones no reader takes yet: recorded for whoever looks at a trace
PHASE_ARGS_UNREAD = [("decoded", "step_block"), ("rows", "admission"), ("bucket", "admission"),
                     ("rids", "admission"), ("active", "launch"), ("rows", "cache_plan"),
                     ("rows", "cache_commit")]


@pytest.mark.parametrize("name,produced", SERVING_READS, ids=[n for n, _ in SERVING_READS])
def test_serving_read_is_produced(run, name, produced):
    assert produced(run), name


@pytest.mark.parametrize("key", MOE_READS)
def test_routing_counter_is_produced(run, moe_run, key):
    """Counted by the fused decode of a model with experts, and only there."""
    assert moe_run.engine.stats[key] > 0
    assert run.engine.stats[key] == 0
    if key == "moe_assignments":      # at least one live row chose top_k experts a layer step
        assert moe_run.engine.stats[key] >= moe_run.engine.stats["moe_layer_steps"] * 2


@pytest.mark.parametrize("key", ROUTED_READS)
def test_routed_assignment_counter_is_produced(run, moe_run, key):
    """``moe.local_assignment_share`` divides by it: in ``engine.stats`` of
    every engine, counted with the other three, and all of them local where
    the layer holds every expert it routes over."""
    assert key in dict(run.engine.stats.items()) and run.engine.stats[key] == 0
    assert moe_run.engine.stats[key] == moe_run.engine.stats["moe_assignments"] > 0


@pytest.mark.parametrize("key", MOE_INSERT_STATS)
def test_insert_routing_counter_is_produced(run, moe_run, key):
    """Counted by the paged insert of a model with experts over its REAL
    tokens (the bucket's padding chooses nothing), and only there: prompts of
    6 and 9 tokens, two layers, top-2, in buckets of 16."""
    stats = moe_run.engine.stats
    assert run.engine.stats[key] == 0
    want = {"moe_insert_assignments": (6 + 9) * 2 * 2,
            "moe_insert_layer_calls": 2 * stats["inserts"],
            "moe_insert_passes": 2 * stats["inserts"],     # every expert held: one a call
            "moe_insert_rows": 2 * stats["inserted_requests"] * 16 * 2}
    if key in want:
        assert stats[key] == want[key]
    elif key == "moe_insert_experts_touched":
        # experts with a real token, a layer call: some, and no more than held
        assert 0 < stats[key] <= stats["moe_insert_layer_calls"] * 8
    # the share of the grouped rows that was real work can be read
    assert 0 < stats["moe_insert_assignments"] / stats["moe_insert_rows"] < 1
    # ... and the share of the rows the kernel multiplied: summed over the
    # inserts' layer calls, no fewer than the real rows and no more than a
    # whole tile a visit (these inserts are one tile of rows x 16 x top-2 rows,
    # one sub-tile, a visit a touched expert: what the parent's kernel ran)
    tile = stats["moe_insert_rows"] // stats["moe_insert_layer_calls"]
    assert (stats["moe_insert_assignments"] <= stats["moe_insert_rows_multiplied"]
            == stats["moe_insert_experts_touched"] * tile)


SPARSE_READS = ["dsa_tokens_visible", "dsa_tokens_selected", "dsa_latent_slots_read"]


@pytest.fixture(scope="module")
def sparse_run():
    """A tiny DeepSeek-V3.2: prompts of 9 and 12 tokens under ``index_topk`` 8,
    so every decode step of both rows chooses."""
    from neuronx_distributed_tpu.models.deepseek_v32 import (
        DeepseekV32Config,
        DeepseekV32ForCausalLM,
    )

    cfg = DeepseekV32Config(**dict(
        TINY, num_layers=3, num_kv_heads=4, kv_lora_rank=16, q_lora_rank=24,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, first_k_dense=1,
        moe_intermediate_size=32, router_experts=16, num_experts=4, n_group=4, topk_group=2,
        top_k=4, index_topk=8, index_n_heads=2, index_head_dim=16))
    rng = np.random.RandomState(3)
    return _serve(cfg, DeepseekV32ForCausalLM,
                  [rng.randint(1, 128, (n,)).astype(np.int32) for n in (9, 12)])


@pytest.mark.parametrize("key", SPARSE_READS)
def test_sparse_counter_is_produced(run, moe_run, sparse_run, key):
    """Counted by the fused blocks of a model whose attention chooses what it
    reads, under the names ``benchmark/layer_metrics/dsa.*.py`` read, and only
    there: a live row a layer-step sees its tokens, is chosen ``index_topk`` of
    them, and the step reads the rung's rows as far as the walk goes."""
    stats = sparse_run.engine.stats
    for other in (run, moe_run):
        assert key in dict(other.engine.stats.items()) and other.engine.stats[key] == 0
    layers, topk = 3, 8
    # a row's BUDGET - 1 tokens after its first take two blocks, and the device
    # keeps a row live to the end of its last block (the host drops the rest)
    steps = -(-(BUDGET - 1) // BLOCK_STEPS) * BLOCK_STEPS
    assert stats["dsa_tokens_selected"] == layers * topk * 2 * steps
    # the row of prompt n sees n + 1, n + 2, .. tokens at its steps
    assert stats["dsa_tokens_visible"] == layers * sum(
        n + t for n in (9, 12) for t in range(1, steps + 1))
    assert stats["dsa_latent_slots_read"] == layers * stats["kv_walk_row_slots"] > 0
    readers = {name: (BENCHMARK / "layer_metrics" / f"dsa.{name}.py").read_text()
               for name in ("selected_share", "latent_read_over_selected", "decode_step_mfu_share",
                            "insert_mfu_share")}
    assert any(f'"{key}"' in text for text in readers.values()), key


@pytest.mark.parametrize("key", SSM_READS)
def test_state_counter_is_produced(run, moe_run, hybrid_run, key):
    """Counted by the programs of a model with per-slot state, and only
    there: two inserts of one row (6 and 9 real tokens of 16 positions, two
    chunks of 8)."""
    stats = hybrid_run.engine.stats
    for other in (run, moe_run):
        assert key in dict(other.engine.stats.items()) and other.engine.stats[key] == 0
    want = {"ssm_scan_tokens": 6 + 9, "ssm_scan_positions": 2 * 16}
    assert stats[key] == want[key] > 0


@pytest.mark.parametrize("name", sorted(SSM_SCOPES))
def test_state_scope_is_in_the_compiled_programs(hybrid_run, name):
    """In the op names of the insert (the chunked scan, the slot rows' gather
    and scatter) or of the fused decode (the recurrence's one step), or both."""
    found = {fused: re.search(rf'op_name="[^"]*\b{name}\b', program.as_text()) is not None
             for fused, program in ((True, hybrid_run.fused), (False, hybrid_run.insert))}
    where = SSM_SCOPES[name]
    assert found[True if where is None else where]
    if where is not None:
        assert not found[not where]
    else:
        assert found[False]


@pytest.mark.parametrize("key", STARTUP_READS)
def test_startup_counter_is_produced(run, key):
    """Present (a reader answers None without its key), a whole number as
    ``engine_stats`` keeps it, and non-trivial where the CPU makes it so: the
    run traced, lowered and compiled programs (no cache: every compile is the
    backend's), ran eager ops, and drew its weights with no weights' program."""
    stats = dict(run.engine.stats.items())
    assert key in stats and stats[key] == int(stats[key]) >= 0
    if key in ("startup_trace_lower_ms", "startup_xla_compile_ms", "startup_unnamed_ms"):
        assert stats[key] > 0


@pytest.mark.parametrize("key", INSERT_HOST_OPS)
def test_insert_host_op_counter_is_produced(run, moe_run, key):
    """One of each an insert, dense or with experts (whose routing sums come
    with the first tokens, in the same fetch); in ``engine.stats.items()``,
    which is what the driver writes into the record."""
    for r in (run, moe_run):
        assert r.engine.stats[key] == r.engine.stats["inserts"] > 0
        assert key in dict(r.engine.stats.items())


@pytest.mark.parametrize("key", WALK_STATS)
def test_walk_counter_is_produced(run, moe_run, key):
    """Counted by every fused decode block, dense or with experts, over the
    steps that had a live row. These tables are one chunk long (64 slots), so
    every such step read all of them."""
    for r in (run, moe_run):
        stats = r.engine.stats
        assert key in dict(stats.items())
        assert 0 < stats["kv_walk_steps"] <= stats["decode_blocks"] * BLOCK_STEPS
        assert stats["kv_walk_tokens"] == stats["kv_walk_steps"] * r.lm.config.max_seq_len
        # ... of every row: a table of one chunk has one rung
        assert stats["kv_walk_row_slots"] == stats["kv_walk_tokens"] * r.lm.max_batch


@pytest.mark.parametrize("async_loop", [False, True], ids=["sync", "async"])
def test_walk_counters_equal_a_python_model_of_the_same_lengths(async_loop):
    """Three requests admitted together into a table of 512 slots (chunks of
    128): the counters against a model of the lengths the host knows. A row
    is live on the device through every step of a block it began unfinished
    (its budget is the host's to latch, between blocks; the pipelined loop
    predicts it at dispatch, so both loops count the same); a step reads whole
    chunks up to its longest live row's reach, the token it writes included."""
    cfg = LlamaConfig(**dict(TINY, max_seq_len=512))
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(128,), max_batch=4, page_size=16)
    engine = ServeEngine(lm, block_steps=BLOCK_STEPS, rng=jax.random.key(0),
                         async_loop=async_loop)
    rng = np.random.RandomState(2)
    prompts, budgets = (100, 125, 30), (9, 14, 5)
    for n, budget in zip(prompts, budgets):
        engine.submit(rng.randint(1, 128, (n,)).astype(np.int32), max_new_tokens=budget,
                      arrival_block=0)
    while engine.step_block():
        pass
    assert [len(c.tokens) for c in sorted(engine.completed, key=lambda c: c.request_id)] \
        == list(budgets)
    tokens = steps = row_slots = 0
    for block in range(max(budgets)):
        # a request holds 1 + block * K tokens when the block starts (the insert gave one)
        live = [n for n, budget in zip(prompts, budgets) if 1 + block * BLOCK_STEPS < budget]
        for step in range(BLOCK_STEPS if live else 0):
            reach = max(live) + block * BLOCK_STEPS + step + 1
            tokens += -(-reach // 128) * 128
            steps += 1
            # of the rows of the smallest rung (1 or 4 of 4 slots: chunks of 128 tokens
            # go by the switch, one rung below the top) that holds the live ones
            row_slots += next(r for r in (1, 4) if r >= len(live)) * -(-reach // 128) * 128
    assert (engine.stats["kv_walk_tokens"], engine.stats["kv_walk_steps"]) == (tokens, steps)
    assert engine.stats["kv_walk_row_slots"] == row_slots
    assert tokens < row_slots < tokens * lm.max_batch      # three live at first, one at last


@pytest.mark.parametrize("async_loop", [False, True], ids=["sync", "async"])
def test_walk_row_slots_are_the_whole_rectangle_when_every_row_is_live(async_loop):
    """Two slots, two requests of one budget admitted together: every step
    with a live row has both live, the top rung, the parent's read."""
    cfg = LlamaConfig(**dict(TINY, max_seq_len=512))
    params = tiny.make_params(LlamaForCausalLM, cfg, seed=0)
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(128,), max_batch=2, page_size=16)
    engine = ServeEngine(lm, block_steps=BLOCK_STEPS, rng=jax.random.key(0),
                         async_loop=async_loop)
    rng = np.random.RandomState(3)
    for n in (120, 40):
        engine.submit(rng.randint(1, 128, (n,)).astype(np.int32), max_new_tokens=13,
                      arrival_block=0)
    while engine.step_block():
        pass
    stats = engine.stats
    assert stats["kv_walk_steps"] > 0
    assert stats["kv_walk_row_slots"] == stats["kv_walk_tokens"] * lm.max_batch


def _complete_spans(r, name):
    return [e for e in r.engine.tracer.events(name.strip('"')) if e["ph"] == "X"]


@pytest.mark.parametrize("name,track", PHASE_SPANS, ids=[n.strip('"') for n, _ in PHASE_SPANS])
def test_phase_span_is_produced(run, name, track):
    """On the track the helper looks on (a request's own lane for ``queued``),
    with a start, a duration and the round's number."""
    spans = _complete_spans(run, name)
    assert spans and all(e["dur"] >= 0 and e["block"] is not None for e in spans)
    if track is None:
        assert {e["lane"] for e in spans} == {("req", rid) for rid in run.ids}
    else:
        assert {e["lane"][1] for e in spans} == {track}
    if name == '"step_block"':
        assert len(spans) == run.engine.stats["blocks"] + 1      # the last found nothing to do
    if name == '"admission"':
        assert len(spans) == run.engine.stats["inserts"]


@pytest.mark.parametrize("arg,span", PHASE_ARGS_READ + PHASE_ARGS_UNREAD,
                         ids=[f"{s}.{a}".replace('"', "") for a, s in PHASE_ARGS_READ + PHASE_ARGS_UNREAD])
def test_phase_span_arg_is_produced(run, arg, span):
    arg = arg.strip('"')
    values = [e["args"][arg] for e in _complete_spans(run, span)]
    assert values
    if arg in ("worked", "decoded"):
        assert {type(v) for v in values} == {bool} and any(values) and not all(values)
    elif arg == "rids":
        assert sorted(rid for v in values for rid in v) == sorted(run.ids)
    elif arg == "decoding":       # two slots: the second pair was admitted beside nobody or one row
        assert all(isinstance(v, int) and 0 <= v <= 2 for v in values) and values[0] == 0
    else:
        assert all(isinstance(v, int) and v > 0 for v in values)


def test_paged_insert_program_is_built_from_rows_and_bucket(run):
    """``benchmark/aot_check.py`` builds a cell's insert programs with two
    arguments, and gets the engine's: the same object, keyed (rows, bucket)."""
    assert run.lm._paged_insert_programs(1, 16) is run.insert
    assert run.lm._paged_insert[(1, 16)] is run.insert
    assert hasattr(run.insert, "memory_analysis") and hasattr(run.insert, "as_text")


@pytest.mark.parametrize("name", TRAIN_READS)
def test_train_step_module_name(train_step_text, name):
    assert re.search(rf"module @{name}\b", train_step_text)


def test_every_name_is_still_read():
    """Each name of the tables occurs in the benchmark's sources: a reader
    that stopped reading a name takes its case away with it."""
    files = [*sorted((BENCHMARK / "drivers").glob("*.py")),
             *sorted((BENCHMARK / "layer_metrics").glob("*.py")),
             BENCHMARK / "trace_parts.py", BENCHMARK / "trace_reduce.py",
             BENCHMARK / "phase_spans.py"]
    text = "\n".join(f.read_text() for f in files)
    names = ([n for n, _ in SERVING_READS] + MOE_READS + ROUTED_READS + SSM_READS + TRAIN_READS
             + STARTUP_READS + [n for n, _ in PHASE_SPANS] + [n for n, _ in PHASE_ARGS_READ])
    assert len(set(names)) == len(names)
    missing = [n for n in names if n not in text]
    assert not missing, missing
