"""Every Pallas kernel of the main path compiles for a TPU v5e — checked
here, without a chip.

The TPU compiler is installed with jaxlib and compiles for a device that is
DESCRIBED, not attached (``jax.experimental.topologies``). Interpret mode,
which every other kernel test runs in, cannot see what Mosaic refuses: the
paged decode kernel passed all of them and was refused at every shape for
squeezing the second-minor dimension out of its K/V blocks. These cases
hold the shapes the chip smoke runs (Llama-2-7B widths); each asserts that
the compiled program contains the kernel as a ``tpu_custom_call``. Nothing
executes, so nothing here says the results are right or fast — that is
``chip_smoke.py``'s job, on the chip.

The file name sorts first so the tier-1 clock always reaches it.
"""

import dataclasses
import math
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from flax.core import meta
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from neuronx_distributed_tpu.inference import CausalLM, SlotSampler
from neuronx_distributed_tpu.kernels import mode
from neuronx_distributed_tpu.kernels.flash_attn import (
    default_attention_blocks,
    flash_attention,
)
from neuronx_distributed_tpu.kernels.ssm_step import ssm_step
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.optimizer.fused_kernel import fused_adamw_leaf
from tests import tiny

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))
from big_ops import big_ops  # noqa: E402 — the listing tool's reader of a compiled text

HEAD_DIM = 128


@pytest.fixture(scope="module")
def chip():
    """Sharding on one described v5e chip."""
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to test
        pytest.skip(f"cannot describe a v5e topology: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _mosaic_not_interpreter(monkeypatch):
    """The process's backend is the CPU, so ``kernels/mode.py`` would pick
    the interpreter: steer it here, in the test. The persistent compilation
    cache goes off around these compiles — an entry written for a described
    device cannot be read back without a chip and would warn on every later
    run."""
    monkeypatch.setattr(mode, "interpret_kernels", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_calls(fn, *avals) -> int:
    return jax.jit(fn).lower(*avals).compile().as_text().count("tpu_custom_call")


@pytest.mark.parametrize("n_kv", [32, 8], ids=["mha32", "gqa32_8"])
def test_flash_attention_fwd_bwd(chip, n_kv):
    b, h, s = 8, 32, 2048
    blk_q, blk_k = default_attention_blocks(s)
    q = jax.ShapeDtypeStruct((b, h, s, HEAD_DIM), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, n_kv, s, HEAD_DIM), jnp.bfloat16,
                              sharding=chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, block_q=blk_q, block_k=blk_k)
        return out.astype(jnp.float32).sum()

    # forward, dK/dV and dQ kernels
    assert _kernel_calls(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv) >= 3


def test_gather_decode_step_holds_no_widened_slab(chip):
    """The single-token decode step of the default (gather) path at the
    long-context cell's shape: GQA 32/8, head 128, bf16 pages of 16, batch 8,
    ``max_seq_len`` 4096, two scanned layers. ``cached_attention`` contracts
    grouped by KV head in the cache's dtype, so the compiled step holds no
    float32 array as large as the gathered slab (K and V repeated per query
    head and widened are two ``f32[8,4096,32,128]`` a layer, 537 MB each),
    and its temporaries stay under HALF of one layer's K pool (under 1 MiB
    measured: the gathered K/V pair lives in the scan body's own space). The
    page pools are the layer scan's carry, donated and written in place: no
    copy of them and no layer's share of them is held (PR 27; as the scan's
    xs/ys they were copied once, 538.5 MB of temporaries in all, and
    1 343 MB with the repeat)."""
    b, s_max, n_kv, page = 8, 4096, 8, 16
    cfg = dataclasses.replace(
        LlamaConfig(vocab_size=256, hidden_size=32 * HEAD_DIM,
                    intermediate_size=1024, num_heads=32, num_kv_heads=n_kv,
                    num_layers=2, max_seq_len=s_max, dtype=jnp.bfloat16,
                    param_dtype=jnp.bfloat16),
        decode=True, remat_policy=None, page_size=page,
        page_pool_pages=b * s_max // page + b)    # slab parity plus scratch pages
    model = LlamaForCausalLM(cfg)
    token = jnp.zeros((b, 1), jnp.int32)
    variables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        meta.unbox(jax.eval_shape(
            lambda: model.init(jax.random.key(0), token))))

    def step(params, cache, ids):
        return model.apply({"params": params, "cache": cache}, ids,
                           mutable=["cache"])

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        variables["params"], variables["cache"],
        jax.ShapeDtypeStruct(token.shape, token.dtype,
                             sharding=chip)).compile()
    slab = b * s_max * n_kv * HEAD_DIM
    widened = {m.group(0) for m in re.finditer(r"f32\[([0-9,]+)\]",
                                               compiled.as_text())
               if math.prod(map(int, m.group(1).split(","))) >= slab}
    assert not widened, widened
    slab_pair = 2 * slab * 2                      # K and V, bf16
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    print(f"gather decode step temporaries: {temporaries / 2 ** 20:.0f} MiB")
    assert temporaries < slab_pair // 4
    # no layer holds a pool of its own: nothing has that shape
    pool = f"{cfg.page_pool_pages},{page},{n_kv},{HEAD_DIM}]"
    assert "bf16[" + pool not in compiled.as_text()
    assert "bf16[1," + pool not in compiled.as_text()


def _traced_bounds(text):
    """How many ``while`` loops of a compiled module stop at a value the
    program computed (their condition compares with no constant), and how
    many ``conditional``s it holds."""
    traced = 0
    for cond in re.findall(r" while\(.*?condition=%([\w.]+)", text):
        body = re.search(r"^%" + re.escape(cond) + r" \(.*?^}", text, re.M | re.S).group(0)
        traced += "constant(" not in body
    return traced, len(re.findall(r" conditional\(", text))


def _leaf_copies(text, shape, dtype="bf16"):
    """``copy`` instructions that produce a whole cache leaf INSIDE a loop
    body or a branch: every computation but the entry one (where the
    compiler may re-lay-out a donated argument once on the way in and once on
    the way out, as it did before this walk existed: PERF.md section 7). A
    leaf in any shape: the walk reads it flat, layers x pages as one axis
    (one switch inside another made every branch copy the latent pool so:
    PERF.md, PR 40)."""
    layers, pages, *rest = shape
    leaf = [[d for d in dims if d != 1] for dims in (shape, (layers * pages, *rest))]
    found, entry = [], False
    for line in text.splitlines():
        if re.match(r"^(ENTRY )?%[\w.]+ \(", line):
            entry = line.startswith("ENTRY")
            continue
        copied = re.search(r"= " + dtype + r"\[([0-9,]+)\]\S* copy\(", line)
        if not entry and copied and [d for d in map(int, copied.group(1).split(",")) if d != 1] in leaf:
            found.append(line.strip()[:160])
    return found


def _page_gathers(text, page_shape, dtype="bf16"):
    """``{(rows, pages): count}`` of the ``gather`` instructions of a compiled
    module that bring whole pages ``page_shape`` out of a pool: what each
    branch of the walk reads (a rung of ONE row has no row axis left)."""
    tail = ",".join(map(str, page_shape))
    found = {}
    for lead in re.findall(r"= " + dtype + r"\[([0-9,]*)" + tail + r"\]\S* gather\(", text):
        dims = [int(d) for d in lead.split(",") if d]
        key = (1, dims[0]) if len(dims) == 1 else tuple(dims)
        found[key] = found.get(key, 0) + 1
    return found


@pytest.mark.parametrize("s_max", [4096, 1024], ids=["loop_4096", "switch_1024"])
def test_gather_decode_step_reads_as_far_as_its_rows_reach(chip, s_max):
    """The same step (GQA 32/8, head 128, bf16 pages of 16, batch 8, two
    scanned layers), told which rows are live as the fused session decode
    tells it: the cache is read in chunks up to a bound the program computes,
    of the rung of rows (1, 2, 4, 8 by the loop; 2, 8 by the switch) that
    holds the live ones (``models/llama.py::KVWalk``). At 4 096 slots by a
    loop: one ``conditional`` over the rungs, in each a ``while`` whose
    condition holds no constant, and no value of the gathered slab's shape
    ``(8, 4096, 8, 128)`` anywhere. At 1 024 slots by a switch over (prefix,
    rung): one ``conditional``, whose widest branch is the old read. Either way a
    branch's gathers bring ITS rung's rows and no more (K and V: two of each
    shape, and of all 8 rows only in the top rung), no pool leaf is copied
    into the loop or a branch (a pool-sized copy in the layer body costs
    milliseconds a step: PR 29 met one under a kernel), and the temporaries
    stay as small as the whole read's."""
    b, n_kv, page, layers = 8, 8, 16, 2
    cfg = dataclasses.replace(
        LlamaConfig(vocab_size=256, hidden_size=32 * HEAD_DIM,
                    intermediate_size=1024, num_heads=32, num_kv_heads=n_kv,
                    num_layers=layers, max_seq_len=s_max, dtype=jnp.bfloat16,
                    param_dtype=jnp.bfloat16),
        decode=True, remat_policy=None, page_size=page,
        page_pool_pages=b * s_max // page + b)
    model = LlamaForCausalLM(cfg)
    variables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        meta.unbox(jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((b, 1), jnp.int32)))))

    def step(params, cache, ids, live):
        return model.apply({"params": params, "cache": cache}, ids, live=live,
                           mutable=["cache"])

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        variables["params"], variables["cache"],
        jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=chip),
        jax.ShapeDtypeStruct((b, 1), jnp.bool_, sharding=chip)).compile()
    text = compiled.as_text()
    loops, switches = _traced_bounds(text)
    slab = f"[{b},{s_max},{n_kv},{HEAD_DIM}]"
    chunk_pages = s_max // 8 // page
    if s_max == 4096:
        reads = [(r, 1) for r in (1, 2, 4, 8)]    # a loop a rung; a turn reads one chunk
        assert loops == len(reads) and switches == 1
        assert slab not in text
    else:
        reads = [(r, n) for r in (2, 8) for n in range(1, 9)]    # one rung below the top
        assert loops == 0 and switches == 1
        assert slab in text                       # the widest branch: the check can see it
    assert _page_gathers(text, (page, n_kv, HEAD_DIM)) == {
        (r, n * chunk_pages): 2 for r, n in reads}
    assert not _leaf_copies(text, (layers, cfg.page_pool_pages, page, n_kv, HEAD_DIM))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * layers * cfg.page_pool_pages * page * n_kv * HEAD_DIM * 2
    assert memory.temp_size_in_bytes < b * s_max * n_kv * HEAD_DIM * 2   # under one K slab


def test_latent_decode_step_reads_as_far_as_its_rows_reach(chip):
    """The benchmark's DeepSeek-V2 rehearsal configuration (bf16, pages of
    16, batch 8, ``max_seq_len`` 4096) for the described v5e: the absorbed
    decode reads the latent cache by a switch over its prefixes (no carried
    state; chunks of 512 tokens, so no rung of rows but the batch: the
    parent's bodies and no more), in the dense layer's scan and in the expert
    layers' scan, with no loop of a traced bound, no copy of the latent leaf,
    and in every branch a gather of its own rectangle of pages."""
    import json
    from pathlib import Path

    from benchmark import run as harness
    from benchmark.drivers import serving

    root = Path(harness.__file__).resolve().parents[1]
    entry = next(c for c in json.loads((root / "BENCHMARK.json").read_text())["configs"]
                 if c["name"] == "deepseek-v2")
    b, s_max, page = 8, 4096, 16
    preset = serving.model_config(harness.load_config(entry, rehearse=True), False,
                                  max_seq_len=s_max, remat_policy=None)
    cfg = dataclasses.replace(preset, decode=True, page_size=page,
                              page_pool_pages=b * s_max // page + b,
                              moe_mode="capacity_factor")
    model = serving.load(harness.load_config(entry, rehearse=True)["builder"]["model"])(cfg)
    variables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        meta.unbox(jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((b, 1), jnp.int32)))))

    def step(params, cache, ids, live):
        return model.apply({"params": params, "cache": cache}, ids, live=live,
                           mutable=["cache"])

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        variables["params"], variables["cache"],
        jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=chip),
        jax.ShapeDtypeStruct((b, 1), jnp.bool_, sharding=chip)).compile()
    text = compiled.as_text()
    assert cfg.dtype == jnp.bfloat16 and cfg.first_k_dense == 1
    assert _traced_bounds(text) == (0, 2)         # one switch a layer scan
    assert _page_gathers(text, (page, cfg.latent_dim)) == {
        (8, n * s_max // 8 // page): 2 for n in range(1, 9)}     # two scans; no rung but the batch
    assert not _leaf_copies(text, (cfg.num_layers, cfg.page_pool_pages, page, 1, cfg.latent_dim))


def _moe_config(family, **kw):
    from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM, mixtral_8x7b
    from neuronx_distributed_tpu.models.olmoe import OlmoeForCausalLM, olmoe_1b_7b

    preset, cls = {"olmoe": (olmoe_1b_7b, OlmoeForCausalLM),
                   "mixtral": (mixtral_8x7b, MixtralForCausalLM)}[family]
    return preset(**kw), cls


def _compiled_moe_step(chip, family, width, **kw):
    """``(cfg, compiled)`` of two scanned layers of a MoE family at its
    published widths, bf16, pages of 16, batch 8, ``max_seq_len`` 1024: the
    model's part of a decode step (``width`` 1) or of an 8 x ``width`` paged
    insert, told which tokens are real as the serving programs tell it."""
    b, s_max, page = 8, 1024, 16
    preset, cls = _moe_config(
        family, num_layers=2, max_seq_len=s_max, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, **kw)
    cfg = dataclasses.replace(preset, decode=True, remat_policy=None,
                              page_size=page,
                              page_pool_pages=b * s_max // page + b)
    model = cls(cfg)
    variables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        meta.unbox(jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((b, 1), jnp.int32)))))

    def step(params, cache, ids, live):
        return model.apply({"params": params, "cache": cache}, ids,
                           live=live, mutable=["cache"])

    return cfg, jax.jit(step, donate_argnums=(1,)).lower(
        variables["params"], variables["cache"],
        jax.ShapeDtypeStruct((b, width), jnp.int32, sharding=chip),
        jax.ShapeDtypeStruct((b, width), jnp.bool_, sharding=chip)).compile()


@pytest.mark.parametrize("width", [1, 512], ids=["decode_step", "insert_8x512"])
@pytest.mark.parametrize("family", ["olmoe", "mixtral"])
def test_moe_serving_runs_the_grouped_kernel_and_holds_less(chip, family, width):
    """Two scanned layers at OLMoE-1B-7B's (hidden 2048, 16 x 128 MHA with
    QK-norm, 64 experts of 1024, top-8) and Mixtral-8x7B's (hidden 4096, 32/8
    GQA, 8 experts of 14336, top-2) published widths, bf16, pages of 16, batch
    8, ``max_seq_len`` 1024: the decode step and the model's part of an
    8 x 512 paged insert, told which tokens are real (``live``) as the
    serving programs tell them. Serving runs the experts as the grouped
    matmul (``kernels/grouped_matmul.py``: gate, up and the activation are
    one Mosaic call, down another), so (a) nothing in the program has the all-experts shape
    ``(E, tokens, I)`` (OLMoE's insert held 512 MiB of it, Mixtral's 0.94
    GB); (b) nothing has the shape of ONE layer's expert weights either: the
    kernel is handed the whole stack and indexes ``[layer, expert]``
    (``MixtralModel.layer_stack``), where a layer's slice would be copied
    out, all experts of it, at every layer of every step; (c) the
    temporaries are no larger than those of the same program with
    ``moe_mode="all_experts"``, which is what every serving program ran
    before PR 29 (MiB, grouped / all_experts: printed below)."""
    b = 8
    temporaries = {}
    for moe_mode in ("capacity_factor", "all_experts"):
        cfg, compiled = _compiled_moe_step(chip, family, width, moe_mode=moe_mode)
        text = compiled.as_text()
        if family == "olmoe":
            assert "qk_norm" in text
        all_experts_shape = f"[{cfg.num_experts},{b * width},{cfg.intermediate_size}]"
        if moe_mode == "all_experts":
            assert all_experts_shape in text        # the check below can see it
        else:
            assert text.count("tpu_custom_call") >= 2     # gate + up + act, down
            assert all_experts_shape not in text
            for weights in ((cfg.hidden_size, cfg.intermediate_size),
                            (cfg.intermediate_size, cfg.hidden_size)):
                assert "[{},{},{}]".format(cfg.num_experts, *weights) not in text
        temporaries[moe_mode] = compiled.memory_analysis().temp_size_in_bytes
    mib = 2 ** 20
    print(f"{family} width {width} temporaries: grouped "
          f"{temporaries['capacity_factor'] / mib:.0f} MiB, all_experts "
          f"{temporaries['all_experts'] / mib:.0f} MiB")
    assert temporaries["capacity_factor"] <= temporaries["all_experts"] + mib
    if width > 1:   # less than ONE array of the all-experts activations
        assert temporaries["capacity_factor"] < (
            cfg.num_experts * b * width * cfg.intermediate_size * 2)


@pytest.mark.parametrize("top_k", [2, 4], ids=["top2", "top4"])
def test_an_insert_that_holds_every_expert_writes_no_retiled_picks(chip, top_k):
    """The 8 x 512 insert at Mixtral's widths, top-2 as published and top-4
    (Xing's and DBRX's): ``_grouped_whole`` sums a token's picks over the
    LEADING axis of the output gather's ``(top_k, T, H)`` rows. Summed as
    ``(T, top_k, H)`` the few choices lie on the axis the TPU tiles, and the
    compiler wrote the whole array anew in a ``T(2,128)`` / ``T(4,128)``
    tiling before the sum: a ``reshape`` of 64 MiB a Mixtral layer, 112 MiB a
    Xing layer, 3.5 % of ``xing4.0-29b-a4b.score``'s device time (PERF.md,
    PR 61). No op of the compiled program has that shape (the text
    ``scripts/big_ops.py`` reads), the two gathers and the float32 sum's
    ``(T, H)`` result are still there."""
    cfg, compiled = _compiled_moe_step(chip, "mixtral", 512, top_k=top_k)
    text = compiled.as_text()
    T, H = 8 * 512, cfg.hidden_size
    assert text.count("tpu_custom_call") >= 2
    assert f"bf16[{T * top_k},{H}]" in text and f"[{T},{H}]" in text
    retiled = [line.split(" = ")[0].strip() for line in text.splitlines()
               if re.search(rf"= \w+\[{T},{top_k},{H}\]", line)]
    assert not retiled, retiled


def test_mixtral_insert_holds_no_logits_but_the_last_positions(chip):
    """The 8 x 512 paged insert of ``mixtral-8x7b.score`` as ``CausalLM``
    builds it (published widths, two layers, bf16, pages of 16, batch 8,
    ``max_seq_len`` 1024), for the described v5e. The head runs over each
    row's last real position only and the first token is sampled inside the
    program, so (a) nothing in it has the shape of all positions' logits,
    ``(8, 512, 32000)``: not an output (0.24 GiB in bf16 before PR 32; what
    the engine read of it was 8 rows), not a temporary; (b) what it returns
    beside the donated cache and keys is the ``(8, 32000)`` logits, eight
    tokens and five sums; (c) the kernels are still in it."""
    from jax.sharding import NamedSharding, PartitionSpec

    from neuronx_distributed_tpu.parallel import mesh

    mesh.initialize_model_parallel(tensor_model_parallel_size=1,
                                   devices=list(chip.device_set))
    repl = NamedSharding(mesh.get_mesh(), PartitionSpec())
    cfg, cls = _moe_config("mixtral", num_layers=2, max_seq_len=1024,
                           dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
        meta.unbox(jax.eval_shape(lambda: cls(cfg).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))["params"])
    rows, bucket = 8, 512
    lm = CausalLM(cfg, params, cls, buckets=(128, bucket), max_batch=rows,
                  page_size=16)
    compiled = lm._paged_insert_programs(rows, bucket)
    text = compiled.as_text()
    assert text.startswith("HloModule jit_insert_fn")
    assert f"[{rows},{bucket},{cfg.vocab_size}]" not in text
    assert f"[{rows},{cfg.vocab_size}]" in text        # the check can see it
    memory = compiled.memory_analysis()
    returned = memory.output_size_in_bytes - memory.alias_size_in_bytes
    print(f"mixtral 8 x 512 insert returns {returned / 2 ** 10:.0f} KiB beside "
          f"the cache, temporaries {memory.temp_size_in_bytes / 2 ** 20:.0f} MiB")
    assert returned < 2 * rows * cfg.vocab_size * 2
    assert text.count("tpu_custom_call") >= 3    # flash forward, two grouped matmuls


def _described_lm(chip, family, monkeypatch, buckets=(128,), rehearse=None, layers=None):
    """``CausalLM`` of one of this file's configurations on the described
    chip, shapes for parameters: two layers, bf16, pages of 16, batch 8.
    ``rehearse``: DeepSeek-V2's rehearsal sizes (the default) or the cell's,
    and those at ``layers`` layers where given."""
    from jax.sharding import NamedSharding, PartitionSpec

    from neuronx_distributed_tpu.inference import causal_lm
    from neuronx_distributed_tpu.parallel import mesh

    mesh.initialize_model_parallel(tensor_model_parallel_size=1,
                                   devices=list(chip.device_set))
    repl = NamedSharding(mesh.get_mesh(), PartitionSpec())
    # the fused decode commits its example rows to the mesh with device_put,
    # which a described device cannot take: hand it shapes
    # (benchmark/aot_check.py does the same)
    monkeypatch.setattr(causal_lm, "repl_args", lambda *xs: tuple(
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl) for x in xs))
    if family == "llama":         # the long-context cell's widths: the walk is a loop
        cfg, cls = LlamaConfig(
            vocab_size=256, hidden_size=32 * HEAD_DIM, intermediate_size=1024,
            num_heads=32, num_kv_heads=8, num_layers=2, max_seq_len=4096,
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16), LlamaForCausalLM
    elif family in ("deepseek", "laguna", "longcat", "xing"):
        # the benchmark's configuration: DeepSeek-V2's and LongCat-Flash's
        # rehearsal ones, Laguna's as ``laguna-s-2.1.longctx`` runs it
        # (9 layers, 32 experts held, 8192 slots)
        import json

        from benchmark import run as harness
        from benchmark.drivers import serving

        root = Path(harness.__file__).resolve().parents[1]
        name = {"deepseek": "deepseek-v2", "laguna": "laguna-s-2.1",
                "longcat": "longcat-flash-chat", "xing": "xing4.0-29b-a4b"}[family]
        entry = next(c for c in json.loads((root / "BENCHMARK.json").read_text())["configs"]
                     if c["name"] == name)
        loaded = harness.load_config(
            entry, rehearse=family != "laguna" if rehearse is None else rehearse)
        cfg = serving.model_config(
            loaded, False, remat_policy=None,
            max_seq_len={"laguna": 8192, "xing": 1024}.get(family, 4096))
        if layers:
            cfg = dataclasses.replace(cfg, num_layers=layers)
        cls = serving.load(loaded["builder"]["model"])
    else:
        cfg, cls = _moe_config(family, num_layers=2, max_seq_len=4096,
                               dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
        meta.unbox(jax.eval_shape(lambda: cls(cfg).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))["params"])
    # a ring a slot is not served beside the prefix cache (a hit would continue a row)
    return CausalLM(cfg, params, cls, buckets=buckets, max_batch=8, page_size=16,
                    prefix_cache=family != "laguna")


@pytest.mark.parametrize("family", ["llama", "olmoe", "mixtral", "deepseek"])
def test_fused_session_decode_takes_its_rows_as_one_matrix_and_donates_the_cache(
        chip, family, monkeypatch):
    """The fused session decode as ``CausalLM`` builds it, for the described
    v5e: the six rows only the host writes arrive as ONE ``s32[6,8]``
    parameter beside ``tok`` and ``done`` (ISSUE 47), and the new argument
    did not disturb the donation of the cache, argument 1: the aliased bytes
    cover every page leaf, the temporaries stay under one of them (where a
    leaf is larger than the step's activations), and no computation but the
    entry copies a leaf."""
    lm = _described_lm(chip, family, monkeypatch).compile()      # decode first, as serving does
    compiled = lm.compile_session_decode_fused(8, SlotSampler(), 0)
    text = compiled.as_text()
    assert text.startswith("HloModule jit_fused_fn")
    entry = _entry(text)
    small = re.findall(r"= (\w+\[[0-9,]*\])\S* parameter\(", entry)
    assert small.count("s32[6,8]") == 1                  # rows
    assert small.count("s32[8,1]") == 1 and small.count("pred[8]") == 1   # tok, done
    assert "f32[8]" not in small                         # no temperature of its own
    leaves = [leaf for path, leaf in
              jax.tree_util.tree_flatten_with_path(lm._cache_avals())[0]
              if "cached_" in jax.tree_util.keystr(path)]
    assert leaves
    sizes = [math.prod(leaf.shape) * leaf.dtype.itemsize for leaf in leaves]
    memory = compiled.memory_analysis()
    print(f"{family} fused block: temporaries {memory.temp_size_in_bytes / 2 ** 20:.0f} MiB, "
          f"a page leaf {min(sizes) / 2 ** 20:.0f} MiB")
    assert memory.alias_size_in_bytes >= sum(sizes)
    if family != "deepseek":      # the rehearsal's latent leaf is 8 MiB: under its activations
        assert memory.temp_size_in_bytes < min(sizes)
    for leaf in leaves:
        assert not _leaf_copies(text, leaf.shape)


def _entry(text):
    return re.search(r"^ENTRY .*?^}", text, re.M | re.S).group(0)


def _copied(text, dims, dtype):
    """``copy`` instructions of a compiled text (or one computation of it)
    whose result has ``dims``, ones aside, in ``dtype``."""
    want = [d for d in dims if d != 1]
    hlo = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    return [line.strip()[:140] for line in text.splitlines()
            if (m := re.search(r"= " + hlo + r"\[([0-9,]+)\]\S* copy\(", line))
            and [d for d in map(int, m.group(1).split(",")) if d != 1] == want]


@pytest.mark.parametrize("family", ["llama", "olmoe", "mixtral", "deepseek", "laguna", "longcat"])
def test_the_weights_are_held_the_way_the_decode_block_reads_them(chip, family, monkeypatch):
    """``CausalLM``'s real path for the described v5e: ``compile()`` lowers
    ``decode`` first, which asks the compiler how the one-token step reads
    each weight leaf (``Layout.AUTO``: ``CausalLM._ask_formats``), the leaves
    that lie otherwise are re-laid (here shapes, which only say so), and the
    fused block and the inserts are lowered with those formats fixed. The
    attention kernels are stored ``(layers, hidden, heads, head_dim)``, tiled
    over ``(heads, head_dim)`` by default, and the one-token dot wants
    ``hidden`` in the tile: the parent's block copied each such leaf whole at
    its entry, every call (llama at Mistral's head counts: 3 copies, the
    parent's count; 768 MiB a block at 16 layers), and its inserts sliced and
    copied a layer's share inside the layer scan. Now (a) some leaf was
    re-laid; (b) the block's entry computation copies NO re-laid leaf, and no
    other weight leaf of a MiB or more but those the compiler's own answer
    left where they were (DeepSeek-V2's ``kv_a_proj``, 576 columns: the
    default of such a shape is not row-major, and the pinned block re-lays
    28 MiB of it a call where the asking compile did not; PERF.md section 7);
    (c) the 1 x 128 and 8 x 128 inserts copy no layer's slice of a re-laid
    leaf anywhere, or, where a prompt's path wants a third order (a latent
    model's expanded form reads ``k_b_proj`` / ``v_b_proj`` its own way), no
    more of them than the same insert lowered on the weights as loaded: an
    insert does not pay for the step's choice; (d) the cache's donation is as
    it was: the aliased bytes cover every page leaf."""
    # the latent models at their cells' widths (two layers: one dense and one
    # expert layer of DeepSeek-V2): the rehearsal's are too narrow to tile
    from neuronx_distributed_tpu.parallel import mesh

    sizes = dict(rehearse=False, layers=2) if family in ("deepseek", "longcat") else {}
    lm = _described_lm(chip, family, monkeypatch, **sizes)
    lm.compile()
    assert lm.param_relaid_leaves > 0 and lm.param_relaid_bytes > 2 ** 20
    moved = lm.relaid_leaves()
    assert len(moved) == lm.param_relaid_leaves
    kept = [(jax.tree_util.keystr(path), leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(lm.params)[0]
            if jax.tree_util.keystr(path) not in dict(moved)]
    print(f"{family}: {len(moved)} leaves held off the default, "
          f"{lm.param_relaid_bytes / 2 ** 20:.1f} MiB: "
          + ", ".join(f"{path.split('[')[-1]} {leaf.format.layout.major_to_minor}"
                      for path, leaf in moved))
    block = lm.compile_session_decode_fused(8, SlotSampler(), 0)
    entry = _entry(block.as_text())
    for path, leaf in moved:
        assert not _copied(entry, leaf.shape, leaf.dtype), path
    others = {path: _copied(entry, leaf.shape, leaf.dtype) for path, leaf in kept
              if math.prod(leaf.shape) * leaf.dtype.itemsize >= 2 ** 20}
    assert {path.split("'")[-2] for path, copies in others.items() if copies} <= {"kv_a_proj"}
    pages = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(lm._cache_avals())[0]
             if "cached_" in jax.tree_util.keystr(path)]
    assert block.memory_analysis().alias_size_in_bytes >= sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize for leaf in pages)
    def slices_copied(text):
        return sum(len(_copied(text, shape, leaf.dtype))
                   for _, leaf in moved for shape in (leaf.shape, leaf.shape[1:]))

    for rows in (1, 8):
        copied = slices_copied(lm._paged_insert_programs(rows, 128).as_text())
        if copied:
            assert family in ("deepseek", "longcat")
            # an insert lowered first settles the formats as the weights are
            mesh.destroy_model_parallel()        # the same devices again: the same mesh
            loaded = _described_lm(chip, family, monkeypatch, **sizes)
            was = slices_copied(loaded._paged_insert_programs(rows, 128).as_text())
            assert loaded._formats_settled and loaded.param_relaid_leaves == 0
            print(f"{family} {rows} x 128 insert: {copied} slices copied, {was} as loaded")
            assert copied <= was


def _laguna_block(chip, monkeypatch):
    """What the cases below read of the compiled block, once a process."""
    def make():
        lm = _described_lm(chip, "laguna", monkeypatch).compile()
        compiled = lm.compile_session_decode_fused(8, SlotSampler(), 0)
        return dict(
            text=compiled.as_text(), temp=compiled.memory_analysis().temp_size_in_bytes,
            leaf=next(leaf.shape for path, leaf in
                      jax.tree_util.tree_flatten_with_path(lm._cache_avals())[0]
                      if jax.tree_util.keystr(path).endswith("['window_key']")))

    return tiny.built("aot_laguna_block", make)


@pytest.mark.parametrize("rung", [1, 2, 4, 8])
def test_laguna_window_step_moves_its_rows_of_one_ring(chip, monkeypatch, rung):
    """``laguna-s-2.1.longctx``'s fused decode block as ``CausalLM`` builds it
    (published widths, 9 layers: 6 window layers' rings of 528 tokens stacked
    ``(6, 8, 8, 528, 128)``, 72 query heads over 8 KV heads of 128), for the
    described v5e. A window layer's one-token read contracts its ring with the
    KV head as a batch dimension: with the head INSIDE the ring slot the
    compiler re-laid-out the whole stacked leaf (49.5 MiB, K and V) ahead of
    the slice in the branches of rungs 1 and 8, and lowered the array index
    of rungs 2 and 4 as a mini gather that slices all 48 stacked rows
    (``bf16[48,256,8,128]``, four a branch): 96-99 MiB moved a window
    layer-step where a row needs 2.16 MB (PERF.md, PR 50). Head-major, and the
    rows taken by slices: (a) nothing but the write in place has the stacked
    leaf's element count, nothing the mini gather's; (b) under the branch of
    ``rung`` rows nothing is larger than one layer's eight rows, and nothing
    of the ring's dtype larger than the rung's rows."""
    block = _laguna_block(chip, monkeypatch)
    text, leaf = block["text"], block["leaf"]
    layers, b = leaf[:2]
    assert (layers, b, math.prod(leaf[2:])) == (6, 8, 8 * 528 * HEAD_DIM)
    row = math.prod(leaf[2:]) * 2                 # one slot's ring in one layer, bf16
    ops = big_ops(text, 2 ** 14)
    whole = [op for op in ops                     # the stacked leaf, in any shape
             if op["bytes"] == layers * b * row and op["shape"].startswith("bf16")]
    moved = [op for op in whole if not op["in_place"]]
    assert not moved, [(op["kind"], op["op"], op["shape"], op["computation"]) for op in moved]
    assert len(whole) == 2 * 3 and all("ring_write" in op["op_name"] for op in whole)
    gathered = [op for op in ops if op["shape"].startswith(f"bf16[{layers * b},256,")]
    assert not gathered, [(op["op"], op["shape"], op["computation"]) for op in gathered]
    branch = f"attend_window/cond/branch_{(1, 2, 4, 8).index(rung)}_fun"
    under = [op for op in ops if branch in op["op_name"]]
    assert under                                  # the check can see the branch
    assert max(op["bytes"] for op in under) <= b * row
    assert max((op["bytes"] for op in under if op["shape"].startswith("bf16")),
               default=0) <= rung * row
    print(f"laguna fused block: temporaries {block['temp'] / 2 ** 20:.1f} MiB")
    assert block["temp"] < 571 * 2 ** 20  # the parent's 571.6 MiB


@pytest.mark.parametrize("family,bucket,sizes", [
    ("laguna", 4096, (10, 32, 256, 3072, 1024)), ("deepseek", 2048, (6, 20, 160, 5120, 1536))],
    ids=["laguna_1x4096", "deepseek_v2_1x2048"])
def test_a_share_holding_insert_writes_no_array_of_every_pick(chip, monkeypatch, family, bucket,
                                                              sizes):
    """The 1 x 4096 insert of ``laguna-s-2.1.longctx`` and the 1 x 2048 of
    ``deepseek-v2.longctx`` at the cells' sizes, for the described v5e. An
    expert layer holds an eighth of the routed experts: the parent's program
    gathered, allocated and combined every pick (under ``forward_grouped`` four
    arrays of ``bucket x top_k`` rows of ``H`` a layer, 240 MiB each in Laguna,
    and the kernel's gate/up buffer beside them); now a pass holds
    ``row_bound`` rows, a quarter, and nothing under ``forward_grouped`` is as
    large as every pick's row: the gather of ``x``, the kernels' buffers and
    the slots of the weighted sum are the bound's or the tokens'."""
    from neuronx_distributed_tpu.moe.expert_mlps import row_bound

    top_k, held, routed, hidden, inter = sizes
    lm = _described_lm(chip, family, monkeypatch, buckets=(bucket,), rehearse=False)
    cfg = lm.config
    assert (cfg.top_k, cfg.num_experts, cfg.router_experts, cfg.hidden_size,
            cfg.moe_intermediate_size) == sizes
    text = lm._paged_insert_programs(1, bucket).as_text()
    ops = [op for op in big_ops(text, 2 ** 20) if "forward_grouped" in op["op_name"]]
    bound = row_bound(bucket, top_k, held, routed)
    assert bound == bucket * top_k // 4
    every_pick = bucket * top_k * hidden * 2          # bf16
    assert ops and max(op["bytes"] for op in ops) < every_pick // 2, \
        [(op["op"], op["shape"]) for op in ops if op["bytes"] >= every_pick // 2]
    kernels = {op["shape"] for op in ops if op["kind"] == "custom-call"}
    assert kernels == {f"bf16[{bound},{inter}]", f"bf16[{bound},{hidden}]"}
    assert any("/while/body/" in op["op_name"] for op in ops)       # the passes' loop


@pytest.mark.parametrize("family,rows,bucket,slots,sizes,parents", [
    ("xing", 8, 512, 1024, (32, 192), 372_436_992),
    ("longcat", 8, 2048, 4096, (64, 192), 3_390_457_344)],
    ids=["xing_8x512", "longcat_8x2048"])
def test_a_fresh_latent_insert_sweeps_its_own_tokens(chip, monkeypatch, family, rows, bucket,
                                                     slots, sizes, parents):
    """The 8 x 512 insert of ``xing4.0-29b-a4b.score`` and the 8 x 2048 of
    ``longcat-flash-chat.longctx`` at the cells' sizes, for the described v5e.
    Latent attention's prompt form is a ``conditional``: where every row of the
    call starts at 0, the keys and values of the rows' OWN ``bucket`` slots go
    through the flash kernel (all 8 of Xing's rows in one call, LongCat's a
    row a call: ``_one_call``), and nothing that branch expands reaches a
    row's keys at ``max_seq_len``; where a row continues, the other branch, one
    row a call over every slot as before. The pool stays out of the rows' loop
    and of both branches (inside, the compiler copied the stacked leaf whole,
    289 MiB a layer in LongCat's listing). The program's temporaries stay
    within 128 MiB of ``parents``, what the listing of the tree before the
    branch read (commit 0107ef1: Xing's rows in one call hold 120 MiB more,
    LongCat's 0.4), and the whole program within 14.5 GiB: the largest insert
    of any latent cell (LongCat's r7, 13.9) and the 0.6 GiB the chip has to
    spare beside it (ROADMAP S18)."""
    from neuronx_distributed_tpu.models.deepseek_v2 import DeepseekV2Attention

    lm = _described_lm(chip, family, monkeypatch, buckets=(bucket,), rehearse=False)
    cfg = lm.config
    assert (cfg.max_seq_len, cfg.num_heads, cfg.head_dim_) == (slots, *sizes)
    group = rows if DeepseekV2Attention(cfg)._one_call(rows, bucket) else 1
    assert group == (rows if family == "xing" else 1)
    compiled = lm._paged_insert_programs(rows, bucket)
    text = compiled.as_text()
    calls = {"branch_0_fun": set(), "branch_1_fun": set()}      # fresh (false), continued
    for line in text.splitlines():
        if "flash_fwd/pallas_call" in line and "custom_call_target" in line:
            branch = re.findall(r"jit\(_prompt_rows\)/cond/(branch_\d_fun)/", line)[-1]
            calls[branch].add(
                re.search(r"operand_layout_constraints=\{(.*?)\}, front", line).group(1))
    n, d = sizes

    def operands(g, keys):
        q, k = f"bf16[{g * n},{bucket},{d}]{{2,1,0}}", f"bf16[{g * n},{keys},{d}]{{2,1,0}}"
        return ", ".join([q, k, k, f"s32[{g},1,{bucket}]{{2,1,0}}", f"s32[{g},1,{keys}]{{2,1,0}}"])

    assert calls["branch_0_fun"] == {operands(group, bucket)}
    assert calls["branch_1_fun"] == {operands(1, slots)}
    up = [op for op in big_ops(text, 2 ** 20)
          if "cond/branch_0_fun/" in op["op_name"] and "mla_kv_up" in op["op_name"]]
    assert up and max(op["bytes"] for op in up) <= group * n * bucket * d * 2, \
        [(op["op"], op["shape"]) for op in up]
    leaf = next(leaf for path, leaf in jax.tree_util.tree_flatten_with_path(lm._cache_avals())[0]
                if "cached_key" in jax.tree_util.keystr(path))
    assert not _leaf_copies(text, leaf.shape)
    memory = compiled.memory_analysis()
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
             + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    print(f"{family} {rows} x {bucket} insert: temporaries {memory.temp_size_in_bytes} bytes, "
          f"{total} in all")
    assert memory.temp_size_in_bytes <= parents + 128 * 2 ** 20
    assert total <= 14.5 * 2 ** 30


def test_lagunas_decode_block_sorts_one_tile_and_loops_over_no_pass(chip, monkeypatch):
    """A decode step's list (8 rows x top-10 = 80 picks) is one tile: the
    whole list a call, no loop of passes, the kernels' buffers 80 rows."""
    text = _laguna_block(chip, monkeypatch)["text"]
    ops = [op for op in big_ops(text, 2 ** 10) if "forward_grouped" in op["op_name"]]
    assert ops
    kernels = {op["shape"] for op in ops if op["kind"] == "custom-call"}
    assert kernels == {"bf16[80,1024]", "bf16[80,3072]"}
    assert not re.search(r"forward_grouped/[^\"]*while", text)


def test_fused_adamw_leaf(chip):
    leaf = (4096, 11008)

    def aval(dtype, shape=leaf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def fn(g, mu, nu, ms, scalars):
        return fused_adamw_leaf(g, mu, nu, ms, scalars, b1=0.9, b2=0.95,
                                eps=1e-8, wd=0.01, p_dtype=jnp.bfloat16)

    f32 = jnp.float32
    assert _kernel_calls(fn, aval(jnp.bfloat16), aval(f32), aval(f32),
                         aval(f32), aval(f32, (1, 4))) == 1


def test_ssm_step_steps_the_leaf_in_place(chip):
    """granite-4.0-h-micro's state at 16 slots (36 layers x 16 rows of
    (64, 64, 128) float32, 1.2 GB), the kernel called layer after layer from a
    loop whose carry is the donated leaf, as the period scan and the fused
    block's step loop hold it: the alias reaches the argument, so the program
    holds no second leaf, no copy of it and no update of a layer's rows."""
    layers, b, h, p, n = 36, 16, 64, 64, 128
    f32 = jnp.float32

    def aval(*shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def fn(state, decay, dtx, B, C, live):
        def layer(at, carry):
            state, ys = carry
            state, y = ssm_step(state, at * b, decay, dtx, B, C, live)
            return state, ys + y
        return jax.lax.fori_loop(0, layers, layer, (state, jnp.zeros((b, h, p), f32)))

    compiled = jax.jit(fn, donate_argnums=0).lower(
        aval(layers * b, h, p, n), aval(b, h), aval(b, h, p), aval(b, n), aval(b, n),
        aval(b, dtype=jnp.bool_)).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    leaf = layers * b * h * p * n * 4
    assert text.count("tpu_custom_call") == 1
    assert memory.alias_size_in_bytes >= leaf and memory.temp_size_in_bytes < leaf // 100
    rows = re.escape(f"f32[{b},{h},{p},{n}]")
    whole = re.escape(f"f32[{layers * b},{h},{p},{n}]")
    assert not re.search(rf"= (?:{rows}|{whole})\S* (?:copy|dynamic-update-slice|dynamic-slice)\(",
                         text)
