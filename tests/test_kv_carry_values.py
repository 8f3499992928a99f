"""The KV pools as the layer scan's carry: the same values in the same places
(ISSUE 27).

Every layer now writes its rows of ONE buffer per K/V leaf, at ``layer x
pages`` + its block table's page ids. The oracle is the layer class run on a
pool of its OWN per layer (a one-layer ``KVLayerView``), two ways: as a scan
whose xs and ys are the per-layer pools (the form the carry replaced) and as
a Python loop over layers with no scan. Insert logits, the tokens of two fused
blocks and every byte of every layer's pool after them must be the same, bit
for bit, for GQA / MHA with QK-norm / MQA (and MQA with its one KV head repeated
under TP), float32 / bfloat16 / int8 pages, a table of one chunk (the whole
read) and of eight (``KVWalk``: by the loop at 4 096 slots, by the switch at
512), on one device and under 2- and 4-device TP meshes. The logits of one more step come from the stand-alone one-token
program, whose head the CPU compiler rounds 1-2 ulp away from the oracle's on
a mesh without TP (the K/V it writes agree to the bit): held to 2e-6. With
full-precision pages the contiguous slab (which takes the same route, written
at [layer, row, slot]) must agree bit for bit too. A page reused after
``retire`` and writes past ``max_seq_len`` (dropped: with one buffer for all
layers a write that merely left the layer's share would land in the NEXT
layer's first page) each get their cases.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from neuronx_distributed_tpu.inference import CausalLM
from neuronx_distributed_tpu.inference.sampling import SlotSampler
from neuronx_distributed_tpu.models.llama import (
    KVLayerView,
    LlamaConfig,
    LlamaDecoderLayer,
    LlamaForCausalLM,
    kv_leaf_shapes,
)
from neuronx_distributed_tpu.parallel import mesh as psm
from neuronx_distributed_tpu.trainer import (
    initialize_parallel_model,
    neuronx_distributed_config,
)

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=3,
    num_heads=4, num_kv_heads=2, max_seq_len=32, dtype=jnp.float32,
    use_flash_attention=False, remat_policy=None,
)
PAGE, K, B = 4, 4, 2
GQA, MQA = {}, dict(num_kv_heads=1)
MHA_QK = dict(num_kv_heads=4, qk_norm=True)
BF16 = dict(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)

# (attention, model dtype, lm keywords, TP degree[, max_seq_len])
CASES = {
    "gqa-f32-gather-tp1": (GQA, {}, {}, 1),
    "mha_qknorm-f32-gather-tp1": (MHA_QK, {}, {}, 1),
    "mqa-f32-gather-tp1": (MQA, {}, {}, 1),
    "gqa-bf16-gather-tp1": (GQA, BF16, {}, 1),
    "mqa-int8-gather-tp1": (MQA, {}, dict(page_dtype="int8"), 1),
    "gqa-bf16-loop-tp1": (GQA, BF16, {}, 1, 4096),
    "mha_qknorm-int8-gather-tp1": (MHA_QK, {}, dict(page_dtype="int8"), 1),
    "gqa-f32-gather-tp2": (GQA, {}, {}, 2),
    "gqa-int8-gather-tp2": (GQA, {}, dict(page_dtype="int8"), 2),
    "gqa-int8-switch-tp2": (GQA, {}, dict(page_dtype="int8"), 2, 512),
    "mqa_x2-f32-gather-tp2": (dict(num_kv_heads=1, kv_size_multiplier=2), {}, {}, 2),
    "mha_qknorm-f32-gather-tp4": (MHA_QK, {}, {}, 4),
    "mha_qknorm-bf16-gather-tp4": (MHA_QK, BF16, {}, 4),
}


def _params(cfg, tp):
    psm.initialize_model_parallel(tensor_model_parallel_size=tp)
    nxd = neuronx_distributed_config(tensor_parallel_size=tp)
    return initialize_parallel_model(nxd, lambda: LlamaForCausalLM(cfg),
                                     jnp.zeros((1, 8), jnp.int32)).params


def _prompts(n, seed=5):
    rng = np.random.RandomState(seed)
    return rng.randint(1, 127, (n, 8)).astype(np.int32)


def _drive(lm, prompts, lengths, blocks=2):
    """Insert into slots 0..n, ``blocks`` fused greedy blocks of ``K`` steps
    with every slot live, one more step: what a stream's reader would see."""
    session = lm.start_session()
    slots = np.arange(len(prompts))
    kw = dict(reserve_tokens=blocks * K + 2) if lm.paged else {}
    out = {"insert": np.asarray(lm.insert(session, slots, prompts, lengths=lengths, **kw))}
    fused = lm.compile_session_decode_fused(K, SlotSampler(), 0)
    tok = jnp.asarray(out["insert"].argmax(-1)[:, None], jnp.int32)
    lens, counts = np.asarray(lengths, np.int32), np.zeros((B,), np.int32)
    done, tokens = jnp.zeros((B,), bool), []
    for _ in range(blocks):
        toks, session.cache, tok, lens, done = fused(
            lm.params, session.cache, tok, jax.random.split(jax.random.key(1), B), done,
            lm.block_rows(counts, lens, np.ones((B,), bool), np.full((B,), -1),
                          np.ones((B,)), np.ones((B,), bool)))[:5]
        tokens.append(np.asarray(toks))
        counts = counts + K
    out["tokens"] = np.concatenate(tokens)
    out["after"] = np.asarray(lm.step(session, np.asarray(tok)[:, 0]))
    return out, session


def _pools(cache):
    return {jax.tree_util.keystr(path).split("']['")[-1].rstrip("']"): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
            if "cached_" in jax.tree_util.keystr(path)}


def _own_pool_layer(cfg, x, layer_params, small, pools):
    """One decoder layer on ITS OWN small leaves and pool (a one-layer view)."""
    view = KVLayerView(jnp.int32(0), jax.tree.map(lambda p: p[None], pools))
    x, mut = LlamaDecoderLayer(cfg).apply(
        {"params": layer_params, "cache": {"attention": small}},
        x, None, view, mutable=["cache"])
    return x, mut["cache"]["attention"], jax.tree.map(lambda p: p[0], view.leaves)


def _oracle(cfg, params, how):
    """A forward pass in which every layer owns its pool, two ways.

    ``"xs_ys"``: one program, the layers as a ``lax.scan`` whose scanned
    input and output are the per-layer pools, the form the carry replaced.
    ``"python_loop"``: no scan, a one-layer program called once per layer
    from Python (the layer index an argument, as it is a loop counter in the
    scan: as a constant it lets the compiler pick other matmuls). ``last``
    (b,), where given: the head over that one position a row, as the insert
    programs run it (over all positions the compiler picks another matmul
    for it too)."""
    model = LlamaForCausalLM(cfg)
    variables = {"params": params}
    block = params["model"]["layers"]["block"]
    embed = lambda ids: nn.apply(lambda m: m.model.embed(ids), model)(variables)  # noqa: E731

    def head(x, last=None):
        x = nn.apply(lambda m: m.model.final_norm(x), model)(variables)
        if last is not None:
            x = x[jnp.arange(x.shape[0]), last][:, None]
        return nn.apply(lambda m: m._head(x), model)(variables)

    @jax.jit
    def scanned(small, pools, ids, last=None):
        def body(x, xs):
            x, *ys = _own_pool_layer(cfg, x, *xs)
            return x, ys
        x, (small, pools) = jax.lax.scan(body, embed(ids), (block, small, pools))
        return head(x, last), small, pools

    @jax.jit
    def one_layer(x, small, pools, layer):
        pick = lambda tree: jax.tree.map(  # noqa: E731
            lambda p: jax.lax.dynamic_index_in_dim(p, layer, keepdims=False), tree)
        put = lambda tree, new: jax.tree.map(  # noqa: E731
            lambda p, n: jax.lax.dynamic_update_index_in_dim(p, n, layer, 0), tree, new)
        x, new_small, new_pools = _own_pool_layer(cfg, x, pick(block), pick(small), pick(pools))
        return x, put(small, new_small), put(pools, new_pools)

    def looped(small, pools, ids, last=None):
        x = jax.jit(embed)(ids)
        for layer in range(cfg.num_layers):
            x, small, pools = one_layer(x, small, pools, jnp.int32(layer))
        return jax.jit(head)(x, last), small, pools

    return scanned if how == "xs_ys" else looped


def _oracle_drive(cfg, params, how, tables, prompts, lengths, blocks=2):
    forward = _oracle(cfg, params, how)
    stacked = lambda x: jnp.broadcast_to(x, (cfg.num_layers, *x.shape))  # noqa: E731
    small = {"block_table": stacked(jnp.asarray(tables, jnp.int32)),
             "cache_index": jnp.zeros((cfg.num_layers, B), jnp.int32)}
    pools = {n: jnp.zeros((cfg.num_layers, *shape), dtype)
             for n, (shape, dtype) in kv_leaf_shapes(cfg, B).items()}
    # the insert's contract: pad id 0 beyond a row's length, the last real
    # token's logits, cache_index = the true length
    ids = np.where(np.arange(prompts.shape[1])[None] < np.asarray(lengths)[:, None], prompts, 0)
    logits, small, pools = forward(small, pools, jnp.asarray(ids),
                                   jnp.asarray(lengths - 1, jnp.int32))
    out = {"insert": np.asarray(logits)[:, 0]}
    small["cache_index"] = stacked(jnp.asarray(lengths, jnp.int32))
    tok, tokens = out["insert"].argmax(-1), []
    for _ in range(blocks * K):
        logits, small, pools = forward(small, pools, jnp.asarray(tok[:, None], jnp.int32))
        tok = np.asarray(logits)[:, 0].argmax(-1)
        tokens.append(tok)
    out["tokens"] = np.stack(tokens).astype(np.int32)
    logits, small, pools = forward(small, pools, jnp.asarray(tok[:, None], jnp.int32))
    out["after"] = np.asarray(logits)[:, 0]
    return out, {n: np.asarray(p) for n, p in pools.items()}


def _assert_same(got, want, what, logits_atol=0.0):
    """Tokens, pools and (``logits_atol`` 0) logits bit for bit. Against the
    oracle's own programs the two single-position heads (the insert's last
    position, the step after the blocks) are other matmuls of the same sums,
    so their logits are held to ``logits_atol`` and the streams to equality."""
    for name in want:
        assert got[name].dtype == want[name].dtype, (what, name)
        a, b = got[name].astype(np.float32), want[name].astype(np.float32)
        if name in ("insert", "after") and logits_atol:
            np.testing.assert_allclose(a, b, rtol=0, atol=logits_atol, err_msg=f"{what}: {name}")
        else:
            np.testing.assert_array_equal(a, b, err_msg=f"{what}: {name}")


LOOPED = ["gqa-f32-gather-tp1", "mha_qknorm-int8-gather-tp1", "mqa-f32-gather-tp1",
          "gqa-f32-gather-tp2"]


@pytest.mark.parametrize("how,case", [("xs_ys", c) for c in sorted(CASES)]
                         + [("python_loop", c) for c in LOOPED])
def test_carried_pools_match_layers_that_own_theirs(how, case):
    attention, dtype, lm_kw, tp, *seq = CASES[case]
    cfg = LlamaConfig(**{**TINY, **attention, **dtype, **dict(zip(("max_seq_len",), seq))})
    params = _params(cfg, tp)
    prompts, lengths = _prompts(B), np.array([8, 5])
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8,), max_batch=B,
                  page_size=PAGE, **lm_kw).compile()
    got, session = _drive(lm, prompts, lengths)
    want, want_pools = _oracle_drive(lm.config, params, how, session.paged.tables, prompts, lengths)
    _assert_same(got, want, "against layers that own their pools", logits_atol=2e-6)
    _assert_same(_pools(session.cache), want_pools, "pools against the loop's own")
    if "page_dtype" not in lm_kw and how == "xs_ys":
        # pages in the model's own precision hold what the slab holds
        slab = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8,), max_batch=B).compile()
        want, _ = _drive(slab, prompts, lengths)
        _assert_same(got, want, "against the contiguous slab")


@pytest.mark.parametrize("pages", ["float32", "int8"])
def test_page_reused_after_retire(pages):
    """A retired stream's pages come back holding its bytes; the next stream
    to get them must read and (int8: requantise) as if they were new."""
    cfg = LlamaConfig(**TINY)
    params = _params(cfg, 1)
    lm = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8,), max_batch=B,
                  page_size=PAGE, page_pool_pages=10, page_dtype=pages,
                  prefix_cache=False).compile()
    first, second = _prompts(B, seed=7), _prompts(B, seed=8)
    lengths = np.array([8, 6])
    _, session = _drive(lm, first, lengths, blocks=1)
    used = {int(p) for p in session.paged.tables[0]} - set(session.paged.scratch.tolist())
    lm.retire(session, [0])
    slots = np.array([0])
    reused = np.asarray(lm.insert(session, slots, second[:1], lengths=lengths[:1],
                                  reserve_tokens=K + 2))
    assert used & {int(p) for p in session.paged.tables[0]}, "the pool was too large to reuse"
    fresh_session = lm.start_session()
    fresh = np.asarray(lm.insert(fresh_session, slots, second[:1], lengths=lengths[:1],
                                 reserve_tokens=K + 2))
    np.testing.assert_array_equal(reused, fresh)
    tok = np.zeros((B,), np.int32)
    tok[0] = reused.argmax(-1)[0]
    np.testing.assert_array_equal(np.asarray(lm.step(session, tok))[0],
                                  np.asarray(lm.step(fresh_session, tok))[0])


@pytest.mark.parametrize("width", [1, 4], ids=["decode_step", "chunk_tail"])
@pytest.mark.parametrize("pages", ["float32", "int8", "slab"])
def test_writes_past_max_seq_len_are_dropped_in_every_layer(pages, width):
    """Row 0 sits at (``width`` 1) or runs over (a chunk of 4 from two short
    of) ``max_seq_len``; row 1 writes at slot 3. Nothing but the rows' own
    slots may change: not the page after the row's last, and not the next
    layer's first page, where a write aimed just past ONE layer's share of
    the stacked buffer would land."""
    paged = pages != "slab"
    cfg = dataclasses.replace(
        LlamaConfig(**TINY), decode=True,
        **(dict(page_size=PAGE, page_pool_pages=20, page_dtype=pages) if paged else {}))
    S, ppseq = cfg.max_seq_len, cfg.max_seq_len // PAGE
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(_prompts(B)[:, :width])
    variables = model.init(jax.random.PRNGKey(0), ids)
    stacked = lambda x: jnp.broadcast_to(x, (cfg.num_layers, *x.shape))  # noqa: E731
    start = S if width == 1 else S - 2
    tables = np.stack([np.arange(1, 1 + ppseq), np.arange(1 + ppseq, 1 + 2 * ppseq)])
    before = {n: jnp.full((cfg.num_layers, *shape), 3, dtype)
              for n, (shape, dtype) in kv_leaf_shapes(cfg, B).items()}
    small = {"cache_index": stacked(jnp.asarray([start, 3], jnp.int32))}
    if paged:
        small["block_table"] = stacked(jnp.asarray(tables, jnp.int32))
    cache = {"model": {**before, "layers": {"block": {"attention": small}}}}
    _, mut = model.apply({"params": variables["params"], "cache": cache}, ids,
                         mutable=["cache"])
    after = mut["cache"]["model"]
    for name in before:
        changed = np.asarray(after[name] != before[name])
        rows = changed.reshape(*changed.shape[:2], -1).any(-1)          # (L, pages | rows)
        if paged:
            # whole pages (int8 requantises the page it touches), every layer alike
            want = {int(tables[1, 3 // PAGE]), int(tables[1, (3 + width - 1) // PAGE])}
            if width > 1:
                want.add(int(tables[0, ppseq - 1]))
        else:
            want = {1} if width == 1 else {0, 1}
        for layer in range(cfg.num_layers):
            assert set(np.flatnonzero(rows[layer]).tolist()) == want, (name, layer)
        if name in ("cached_key", "cached_value") and pages != "int8":
            slots = changed.reshape(*changed.shape[:3], -1).any(-1)     # (L, pages | rows, slot)
            written = int(slots.sum()) // cfg.num_layers
            assert written == (width if width == 1 else width + 2), (name, written)
