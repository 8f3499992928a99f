"""The names a device trace is read by are in the compiled programs.

``benchmark/trace_parts.py`` tells which part of the model an op belongs to
from the op's jax name stack. Flax names every module and method; the regions
it does not name carry a ``jax.named_scope`` (one vocabulary, listed below by
file), and every ``pallas_call`` a ``name``. Both are metadata only. This
lowers the tiny fused decode, the tiny paged insert and a tiny train step and
reads the names back from the compiled HLO, so that a refactoring that drops
one fails here and not in a trace on the chip.
"""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import trace_parts
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.trainer import (
    create_train_state,
    initialize_parallel_model,
    initialize_parallel_optimizer,
    make_train_step,
    neuronx_distributed_config,
)
from tests import tiny

# scope names by the file that opens them (PERF.md section 3 lists the same)
SCOPES = {
    "inference/causal_lm.py fused_fn": ["sampler", "bookkeeping"],
    "inference/causal_lm.py insert_fn": ["cache_rows", "table_write", "sampler"],
    "models/llama.py _decode_attention": ["kv_write", "kv_gather", "attend"],
    "models/llama.py LlamaAttention": ["qk_norm"],
    "trainer/step.py": ["grad_accumulate", "grad_clip", "optimizer_update"],
    "parallel/loss.py": ["loss"],
}
# DeepSeek-V2's scopes (PR 37). ``scope_parts.json`` is the benchmark's and has
# no rows for the new ones yet: ``kv_write``/``kv_gather``/``attend`` sort as
# ever, the rest fall to their flax module's part or to ``named_other``.
LATENT_SCOPES = {
    "models/deepseek_v2.py DeepseekV2Attention": ["mla_q", "mla_kv_down", "kv_write",
                                                  "kv_gather", "attend"],
    "models/deepseek_v2.py decode only": ["mla_absorb"],
    "models/deepseek_v2.py prompt only": ["mla_kv_up"],
    "moe/routing.py RouterTopK": ["router_groups"],
    "models/deepseek_v2.py DeepseekV2MoELayer": ["shared_expert"],
}
KERNELS = ["flash_fwd", "flash_fwd_window", "flash_bwd_dkv", "flash_bwd_dq", "fused_adamw",
           "grouped_matmul", "ssm_step"]
# Granite-4.0-H's scopes (PRs 44, 45), all inside the flax module ``mamba``.
# ``scope_parts.json`` has no rows for them either (PERF.md section 7).
MAMBA_SCOPES = {
    "models/granite_hybrid.py Mamba2Mixer": ["ssm_in_proj", "ssm_conv", "ssm_gate_norm",
                                             "ssm_out_proj"],
    "fused_decode": ["ssm_step"],                   # one token a row on a state
    "paged_insert": ["ssm_scan", "state_rows"],     # a prompt; causal_lm.py's rows of the slots
}

# Laguna's scopes (PR 49). ``scope_parts.json`` has no rows for the new ones
# (PERF.md section 7): under the flax module ``attention`` they sort as
# attention, the full layers' ``kv_write`` / ``kv_gather`` / ``attend`` as ever.
WINDOW_SCOPES = {
    "models/laguna.py LagunaAttention": ["attend_window", "ring_write", "rope_full",
                                         "rope_window"],
    "models/llama.py LlamaAttention (attention_gate)": ["attn_gate"],
    "models/laguna.py LagunaLayer": ["shared_expert"],
    "paged_insert": ["state_rows", "flash_fwd_window"],     # causal_lm.py's rows of the slots
}

# LongCat-Flash's scopes (PR 52). ``scope_parts.json`` has no rows for them
# (PERF.md section 7); its sub-layers keep the module names the table knows
# (``sub_<i>/attention``, ``sub_<i>/mlp``, ``..._norm``), so attention, ffn and
# norm sort as ever, and the identity sum falls to ``named_other`` under ``moe``.
SCMOE_SCOPES = {
    "models/deepseek_v2.py DeepseekV2Attention (a scale other than 1)": ["mla_lora_scale"],
    "models/longcat_flash.py LongcatFlashLayer": ["scmoe_branch"],
    "moe/layer.py MoE (zero_experts)": ["zero_experts"],
    "moe/routing.py RouterTopK (selection_bias)": ["router_bias"],
}

# DeepSeek-V3.2's scopes (PR 56). ``scope_parts.json`` has no rows for them
# (PERF.md section 7): inside the flax module ``attention`` they sort as
# attention; the index key's write sits under ``kv_write`` and its read under
# ``kv_gather``, so both sort with the latent's, and the read of the chosen is
# the latent ``attend`` under its mask.
SPARSE_SCOPES = {
    "models/deepseek_v32.py DeepseekV32Attention": ["dsa_index", "dsa_scores", "dsa_select"],
    "moe/routing.py RouterTopK (selection_bias, in groups)": ["router_bias", "router_groups"],
}

TINY = dict(vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, kv_size_multiplier=1, max_seq_len=256, dtype=jnp.float32,
            use_flash_attention=True, remat_policy=None)
OP_NAME = re.compile(r'op_name="([^"]*)"')
# instructions that execute nothing of their own
PLUMBING = re.compile(r"\s(parameter|constant|tuple|get-tuple-element|bitcast|iota)\(")
TABLE = trace_parts.load_table()


def census(compiled):
    """(set of scope components, instructions by part) of a compiled program."""
    components, parts = set(), collections.Counter()
    for line in compiled.as_text().splitlines():
        if " = " not in line or PLUMBING.search(line):
            continue
        m = OP_NAME.search(line)
        tf_op = m.group(1) + ":" if m else ""          # the trace's form: a colon ends it
        components.update(trace_parts.scope(tf_op, TABLE))
        parts[trace_parts.part_of({"tf_op": tf_op}, TABLE)] += 1
    return components, parts


def unnamed_share(parts) -> float:
    return parts["unnamed"] / sum(parts.values())


@pytest.fixture(scope="module")
def params():
    return tiny.make_params(LlamaForCausalLM, LlamaConfig(**TINY), seed=0)


def lm_of(model_cls, cfg, params=None, **kw):
    """Two slots behind pages of 16 and one bucket of 128; seeded weights unless given."""
    params = tiny.make_params(model_cls, cfg, seed=0) if params is None else params
    return tiny.serving_lm(model_cls, params, cfg, buckets=(128,), max_batch=2, page_size=16, **kw)


def test_fused_decode_names_its_regions(params):
    lm = lm_of(LlamaForCausalLM, LlamaConfig(**TINY), params)
    components, parts = census(lm.compile_session_decode_fused(4))
    want = SCOPES["inference/causal_lm.py fused_fn"] + ["kv_write", "attend", "kv_gather"]
    assert set(want) <= components
    for part in ("sampler", "bookkeeping", "kv_write", "attend", "attn_proj", "ffn", "norm",
                 "embed_head"):
        assert parts[part] > 0, part
    # what is left without a name: the scan's own plumbing and what the
    # compiler adds (12 % of the instructions here; 40 % before the scopes)
    assert unnamed_share(parts) < 0.2, parts


@pytest.mark.parametrize("seq,inside", [(4096, r"(while/body/.*){3}"), (256, r"branch_\d+_fun/")],
                         ids=["loop", "switch"])
def test_the_walk_keeps_kv_gather_and_attend_on_the_ops_that_do_the_work(params, seq, inside):
    """The fused block reads the cache in chunks up to a bound it computes
    (``KVWalk``): by a loop inside the layer scan inside the step scan at
    4 096 slots, by a switch at 256. The gathers and the contractions sit in
    the loop's body or the switch's branches, and are still ``kv_gather`` and
    ``attend`` to ``trace_parts.py``; the bound's own arithmetic is the
    attention's, and the counter of what was read is ``bookkeeping``."""
    lm = lm_of(LlamaForCausalLM, LlamaConfig(**dict(TINY, max_seq_len=seq)), params)
    text = lm.compile_session_decode_fused(4).as_text()
    found = collections.Counter()
    for line in text.splitlines():
        m = OP_NAME.search(line)
        if not m or PLUMBING.search(line):
            continue
        part = trace_parts.part_of({"tf_op": m.group(1) + ":"}, TABLE)
        if part in ("kv_gather", "attend") and re.search(inside + r"(attend|kv_gather)/", m.group(1)):
            found[part, re.search(r" (gather|dot|convolution|fusion|exponential)\(", line) is not None] += 1
    assert found["kv_gather", True] > 0 and found["attend", True] > 0, found
    components, parts = census(lm.compile_session_decode_fused(4))
    assert {"kv_write", "kv_gather", "attend", "bookkeeping"} <= components
    assert unnamed_share(parts) < 0.2, parts


@pytest.mark.parametrize("program", ["fused_decode", "paged_insert"])
def test_olmoe_names_qk_norm_router_and_experts(program):
    """The tiny OLMoE decode block and paged insert carry ``qk_norm`` (the one
    scope PR 26 added) and flax's own ``moe/router`` and ``moe/experts``,
    which ``scope_parts.json`` has rows for; the grouped matmul (PR 29: the
    kernel and the sort, gathers and weighted sum around it) is called inside
    ``ExpertMLPs``, so all of it is under ``moe/experts`` and none of it
    without a part. A dense model's step carries none of the three."""
    from neuronx_distributed_tpu.models.olmoe import OlmoeConfig, OlmoeForCausalLM

    cfg = OlmoeConfig(**dict(TINY, num_kv_heads=4, intermediate_size=32, num_experts=16,
                             top_k=4, use_flash_attention=False))
    lm = lm_of(OlmoeForCausalLM, cfg)
    compiled = (lm.compile_session_decode_fused(4) if program == "fused_decode"
                else lm._paged_insert_programs(2, 128))
    components, parts = census(compiled)
    assert {"qk_norm", "kv_write", "kv_gather", "attend", "grouped_matmul"} <= components
    assert parts["attention"] > 0 and parts["router"] > 0 and parts["experts"] > 0
    for line in compiled.as_text().splitlines():
        m = OP_NAME.search(line)
        if m and "grouped_matmul" in m.group(1):
            assert trace_parts.part_of({"tf_op": m.group(1) + ":"}, TABLE) == "experts", line
    assert unnamed_share(parts) < 0.2, parts


@pytest.mark.parametrize("program", ["fused_decode", "paged_insert"])
def test_deepseek_v2_names_latent_attention_router_groups_and_shared_expert(program):
    """The tiny DeepSeek-V2 decode block carries the absorbed form's scope and
    not the up-projection's, the paged insert the other way round; both carry
    the latent write and gather under the names the GQA path uses, the group
    selection, the shared expert and the grouped matmul over the held experts."""
    from neuronx_distributed_tpu.models.deepseek_v2 import DeepseekV2Config, DeepseekV2ForCausalLM

    cfg = DeepseekV2Config(**dict(
        TINY, num_layers=3, num_kv_heads=4, kv_lora_rank=16, q_lora_rank=24,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, moe_intermediate_size=32,
        router_experts=16, num_experts=4, n_group=4, topk_group=2, top_k=4,
        use_flash_attention=False))
    lm = lm_of(DeepseekV2ForCausalLM, cfg)
    decode = program == "fused_decode"
    compiled = (lm.compile_session_decode_fused(4) if decode
                else lm._paged_insert_programs(2, 128))
    components, parts = census(compiled)
    want = {n for where, names in LATENT_SCOPES.items() for n in names
            if "only" not in where} | {"grouped_matmul"}
    want.add("mla_absorb" if decode else "mla_kv_up")
    assert want <= components, sorted(want - components)
    assert ("mla_kv_up" if decode else "mla_absorb") not in components
    assert "dense_layers" in components and "layers" in components
    for part in ("kv_write", "kv_gather", "attend", "router", "experts", "ffn", "norm"):
        assert parts[part] > 0, part


@pytest.mark.parametrize("program", ["fused_decode", "paged_insert"])
def test_granite_names_the_mixers_stages_and_the_step_kernel(program):
    """The tiny Granite-4.0-H decode block carries the one-token recurrence
    under ``mamba/ssm_step``, where ``kernels/ssm_step.py`` runs (its ops carry
    the kernel's name too, interpreted here, one custom call on the chip),
    and no chunked scan; the paged insert the other way round, with the slot
    rows' gather and scatter under ``state_rows``."""
    from neuronx_distributed_tpu.models.granite_hybrid import (
        GraniteHybridConfig,
        GraniteHybridForCausalLM,
    )

    cfg = GraniteHybridConfig(**dict(
        TINY, num_layers=4, layer_types=["mamba", "attention"] * 2, mamba_n_heads=8,
        mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=8, use_flash_attention=False))
    lm = lm_of(GraniteHybridForCausalLM, cfg, prefix_cache=False)
    decode = program == "fused_decode"
    compiled = (lm.compile_session_decode_fused(4) if decode
                else lm._paged_insert_programs(2, 128))
    components, _ = census(compiled)
    other = "paged_insert" if decode else "fused_decode"
    want = ({"mamba", "kv_write", "attend"} | set(MAMBA_SCOPES[program])
            | set(MAMBA_SCOPES["models/granite_hybrid.py Mamba2Mixer"]))
    assert want <= components, sorted(want - components)
    assert not set(MAMBA_SCOPES[other]) & components
    under = [m.group(1) for m in map(OP_NAME.search, compiled.as_text().splitlines())
             if m and "ssm_step" in m.group(1)]
    assert all("/mamba/ssm_step/" in name for name in under), under[:3]


@pytest.mark.parametrize("program", ["fused_decode", "paged_insert"])
def test_laguna_names_the_ring_the_window_read_the_gate_and_both_ropes(program):
    """The tiny Laguna decode block and paged insert carry a window layer's
    ring write and its read (one attend over the ring in a step, the windowed
    flash call over the prompt itself in an insert), the gate, a rope a kind,
    the shared expert, and for the full layers the page write and the read
    under the names every GQA model uses; the insert moves the slots' rings
    under ``state_rows`` and gathers no ring."""
    from neuronx_distributed_tpu.models.laguna import (
        FULL,
        SLIDING,
        LagunaConfig,
        LagunaForCausalLM,
    )

    cfg = LagunaConfig(**dict(
        TINY, num_layers=5, head_dim=8, layer_types=[FULL, SLIDING, SLIDING, SLIDING, FULL],
        num_heads_per_layer=[4, 6, 6, 6, 4], sliding_window=8, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, num_experts=8, router_experts=16, top_k=3,
        rope_parameters={FULL: dict(rope_type="yarn", rope_theta=5e5, factor=8.0,
                                    original_max_position_embeddings=64, attention_factor=1.2,
                                    partial_rotary_factor=0.5),
                         SLIDING: dict(rope_type="default", rope_theta=1e4)}))
    lm = lm_of(LagunaForCausalLM, cfg, prefix_cache=False)
    decode = program == "fused_decode"
    compiled = (lm.compile_session_decode_fused(4) if decode
                else lm._paged_insert_programs(2, 128))
    components, parts = census(compiled)
    want = {n for where, names in WINDOW_SCOPES.items() for n in names if where != "paged_insert"}
    want |= {"kv_write", "kv_gather", "attend", "grouped_matmul", "first", "periods"}
    want |= set(WINDOW_SCOPES["paged_insert"]) | {"flash_fwd"} if not decode else set()
    assert want <= components, sorted(want - components)
    if decode:
        assert not set(WINDOW_SCOPES["paged_insert"]) & components
    for part in ("kv_write", "kv_gather", "attend", "router", "experts", "ffn", "norm"):
        assert parts[part] > 0, part
    under = [m.group(1) for m in map(OP_NAME.search, compiled.as_text().splitlines())
             if m and "attend_window" in m.group(1)]
    assert under and all("/attention/" in name.split("attend_window")[0] for name in under), under[:3]


@pytest.mark.parametrize("program", ["fused_decode", "paged_insert"])
def test_longcat_flash_names_the_branch_the_identity_sum_the_bias_and_the_scales(program):
    """The tiny LongCat-Flash decode block and paged insert carry the expert
    branch, the identity experts' sum, the selection bias and the low-rank
    scales, both sub-layers under the module names the parts table sorts
    attention, ffn and norm by, and the latent scopes DeepSeek-V2's carry."""
    from neuronx_distributed_tpu.models.longcat_flash import (
        LongcatFlashConfig,
        LongcatFlashForCausalLM,
    )

    cfg = LongcatFlashConfig(**dict(
        TINY, num_kv_heads=4, kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, moe_intermediate_size=32, router_experts=16,
        num_experts=4, zero_experts=8, top_k=6, use_flash_attention=False))
    lm = lm_of(LongcatFlashForCausalLM, cfg)
    decode = program == "fused_decode"
    compiled = (lm.compile_session_decode_fused(4) if decode
                else lm._paged_insert_programs(2, 128))
    components, parts = census(compiled)
    want = {n for names in SCMOE_SCOPES.values() for n in names}
    want |= {"sub_0.attend", "sub_1.attend", "sub_0.feed", "sub_1.feed", "mla_q", "mla_kv_down",
             "kv_write", "kv_gather", "attend", "grouped_matmul",
             "mla_absorb" if decode else "mla_kv_up"}
    assert want <= components, sorted(want - components)
    assert "router_groups" not in components and "shared_expert" not in components
    for part in ("kv_write", "kv_gather", "attend", "attention", "router", "experts", "ffn", "norm"):
        assert parts[part] > 0, part
    names = [m.group(1) for m in map(OP_NAME.search, compiled.as_text().splitlines()) if m]
    for scope, inside in (("zero_experts", r"/scmoe_branch/.*/moe/$"),
                          ("router_bias", r"/scmoe_branch/.*/moe/router/$"),
                          ("mla_lora_scale", r"/sub_[01]\.attend/attention/mla_(q|kv_down)/$")):
        under = [n for n in names if f"/{scope}/" in n]
        assert under and all(re.search(inside, n.split(f"{scope}/")[0]) for n in under), \
            (scope, under[:3])
    assert unnamed_share(parts) < 0.2, parts


@pytest.mark.parametrize("program", ["fused_decode", "paged_insert"])
def test_deepseek_v32_names_the_indexer_the_scores_and_the_choice(program):
    """The tiny DeepSeek-V3.2 decode block (a table of 512 slots: its longer
    prefixes hold a choice) and paged insert carry the indexer's projections,
    the index scores and the choice inside the attention module, the index
    key's write under ``kv_write`` and its read under ``kv_gather``, the bias
    and the groups of V3's route, and the latent scopes DeepSeek-V2's carry."""
    from neuronx_distributed_tpu.models.deepseek_v32 import (
        DeepseekV32Config,
        DeepseekV32ForCausalLM,
    )

    cfg = DeepseekV32Config(**dict(
        TINY, num_layers=3, num_kv_heads=4, kv_lora_rank=16, q_lora_rank=24,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, first_k_dense=1,
        moe_intermediate_size=32, router_experts=16, num_experts=4, n_group=4, topk_group=2,
        top_k=4, index_topk=32, index_n_heads=2, index_head_dim=16, index_block_q=64,
        max_seq_len=512, use_flash_attention=False))
    lm = lm_of(DeepseekV32ForCausalLM, cfg)
    decode = program == "fused_decode"
    compiled = (lm.compile_session_decode_fused(4) if decode
                else lm._paged_insert_programs(2, 128))
    components, parts = census(compiled)
    want = {n for names in SPARSE_SCOPES.values() for n in names}
    want |= {"mla_q", "mla_kv_down", "kv_write", "kv_gather", "attend", "shared_expert",
             "grouped_matmul", "mla_absorb" if decode else "mla_kv_up"}
    assert want <= components, sorted(want - components)
    assert "dense_layers" in components and "layers" in components
    for part in ("kv_write", "kv_gather", "attend", "attention", "router", "experts", "ffn", "norm"):
        assert parts[part] > 0, part
    names = [m.group(1) for m in map(OP_NAME.search, compiled.as_text().splitlines()) if m]
    for scope in SPARSE_SCOPES["models/deepseek_v32.py DeepseekV32Attention"]:
        under = [n for n in names if f"/{scope}/" in n]
        # inside the attention module; a few ops of a ``switch`` branch carry a
        # stack that starts over at the branch (``cond/branch_1_fun/dsa_select/..``)
        assert under and all("/attention/" in n.split(f"{scope}/")[0]
                             or n.startswith("cond/branch_") for n in under), scope
        assert any("/attention/" in n.split(f"{scope}/")[0] for n in under), scope
    # both leaves are written under kv_write and read under kv_gather
    assert any("/kv_write/attention._index_write/" in n for n in names)
    assert any("/kv_gather/" in n and "attention._chosen" in n for n in names)
    assert unnamed_share(parts) < 0.2, parts


def test_dense_decode_has_no_qk_norm_scope(params):
    components, parts = census(lm_of(LlamaForCausalLM, LlamaConfig(**TINY), params).compile_session_decode_fused(4))
    assert "qk_norm" not in components and parts["router"] == 0 and parts["experts"] == 0


def test_paged_insert_names_its_regions(params):
    components, parts = census(lm_of(LlamaForCausalLM, LlamaConfig(**TINY), params)._paged_insert_programs(2, 128))
    want = (SCOPES["inference/causal_lm.py insert_fn"]
            + SCOPES["models/llama.py _decode_attention"] + ["flash_fwd"])
    assert set(want) <= components
    assert parts["cache_write"] > 0 and parts["kv_gather"] > 0
    # the first token is sampled inside the program (PR 32), under `sampler`
    assert parts["sampler"] > 0 and parts["embed_head"] > 0
    assert unnamed_share(parts) < 0.1, parts


def test_train_step_names_its_regions():
    # a vocabulary whose embedding leaves a device 8 x 1024 elements of
    # optimizer state: the smallest leaf the AdamW kernel takes
    cfg = LlamaConfig(**dict(TINY, vocab_size=2048, max_seq_len=128, remat_policy="attention"))
    nxd = neuronx_distributed_config(
        tensor_parallel_size=2,
        optimizer_config={"zero_one_enabled": True, "grad_clipping": True, "max_grad_norm": 1.0},
        mixed_precision_config={"use_master_weights": True})
    ids = np.random.RandomState(0).randint(1, 128, (8, 128)).astype(np.int32)
    model = initialize_parallel_model(nxd, lambda: LlamaForCausalLM(cfg), jnp.asarray(ids))
    opt = initialize_parallel_optimizer(nxd, model, learning_rate=1e-3, weight_decay=0.0)
    state = create_train_state(model, opt)

    def loss_fn(p, batch, rng):
        return model.module.apply({"params": p}, batch["ids"], batch["labels"],
                                  method=LlamaForCausalLM.loss)

    step = make_train_step(model, opt, loss_fn, grad_accum_steps=2, optimizer_kernel=True)
    batch = {"ids": ids, "labels": ids}
    components, parts = census(step.lower(state, batch, jax.random.key(0)).compile())
    want = (SCOPES["trainer/step.py"] + SCOPES["parallel/loss.py"]
            + ["flash_fwd", "flash_bwd_dkv", "flash_bwd_dq", "fused_adamw"])
    assert set(want) <= components, sorted(components)
    for part in ("loss", "optimizer", "attention", "ffn", "collective"):
        assert part == "collective" or parts[part] > 0, part
    assert unnamed_share(parts) < 0.2, parts


def test_the_vocabulary_is_the_parts_table():
    """Every scope this test knows is claimed by a row of scope_parts.json
    (not left to ``named_other``), and no two kernels share a name."""
    for where, names in SCOPES.items():
        under = ("layers/block/attention/attention._decode_attention/" if "_decode" in where
                 else "layers/block/attention/" if "llama" in where else "")
        for name in names:
            part = trace_parts.part_of({"tf_op": f"jit(f)/while/body/{under}{name}/add:"}, TABLE)
            # qk_norm has no row of its own: it is attention's, by flax's module name
            allowed = ("attention",) if name == "qk_norm" else ()
            assert part not in {"named_other", "unnamed", "attention"} - set(allowed), (name, part)
    assert len(set(KERNELS)) == len(KERNELS)
