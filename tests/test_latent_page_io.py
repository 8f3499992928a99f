"""The newest cache kinds through what moves and sizes a cache BY PAGES.

Page IO (host tier, handoff, corruption replay) and the sizing formulas find
a model's pages by leaf-name suffix (``engine._KV_PAGE_LEAVES``,
``CausalLM.kv_cache_bytes``). A latent (MLA) page is ONE leaf named
``cached_key``, one head wide and ``kv_lora_rank + qk_rope_head_dim`` deep,
with no value leaf beside it (``models/deepseek_v2.py``), under TWO layer
scans; a hybrid model's pages are stacked over its attention layers only and
its per-slot state is counted apart (``models/granite_hybrid.py``). The
sibling suites drive all of this with GQA pages alone.

* a tiny DeepSeek-V2 through host-tier spill -> restore, prefill -> decode
  handoff -> adopt, and ``inject_page_corruption`` -> replay: the streams are
  the undisturbed run's, in float32 and in bfloat16 (int8 latent pages are
  refused at construction: ``tests/test_deepseek_v2.py``);
* ``kv_cache_bytes`` / ``kv_page_bytes`` / ``kv_page_bytes_host`` of the
  latent and of the hybrid configuration against a count made here from
  ``kv_leaf_shapes``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference import CausalLM, DisaggRouter, Sampler, ServeEngine
from neuronx_distributed_tpu.models.deepseek_v2 import DeepseekV2Config, DeepseekV2ForCausalLM
from neuronx_distributed_tpu.models.granite_hybrid import (
    GraniteHybridConfig,
    GraniteHybridForCausalLM,
)
from tests import tiny
from tests.tiny import world

LATENT = dict(vocab_size=128, hidden_size=32, intermediate_size=48, num_layers=2, num_heads=4,
              num_kv_heads=4, kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=8, first_k_dense=1, moe_intermediate_size=16,
              n_shared_experts=1, num_experts=4, n_group=2, topk_group=1, top_k=2,
              max_seq_len=64, use_flash_attention=False, remat_policy=None,
              moe_mode="capacity_factor")
PERIOD = ("mamba",) * 2 + ("attention",) + ("mamba",) * 2
HYBRID = dict(vocab_size=128, hidden_size=32, intermediate_size=48, num_layers=10, num_heads=4,
              num_kv_heads=2, head_dim=8, layer_types=PERIOD * 2, mamba_n_heads=8,
              mamba_d_head=8, mamba_d_state=8, mamba_chunk_size=8, max_seq_len=64,
              use_flash_attention=False, remat_policy=None)
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
K, PAGE, ROWS = 4, 4, 3
SMALL_POOL = 13     # 3 scratch + 10 allocatable: tests/test_kv_tier.py's pressure
TIER = 32


def latent_lm(dtype, pool_pages=None):
    """A tiny DeepSeek-V2 (a dense layer's scan, then an expert layer's) behind
    a pool of ``pool_pages`` (None: room for every row); one weight set a
    dtype, every lm built once."""
    world()
    cfg = DeepseekV2Config(**LATENT, dtype=DTYPES[dtype], param_dtype=DTYPES[dtype])
    params = tiny.built(("latent_page_io", dtype), lambda: tiny.make_params(
        DeepseekV2ForCausalLM, cfg, seed=0))
    return tiny.built(("latent_page_io", dtype, pool_pages), lambda: CausalLM(
        cfg, params, DeepseekV2ForCausalLM, buckets=(16, 32), max_batch=ROWS, page_size=PAGE,
        page_pool_pages=pool_pages).compile())


def family(seed, tails, tail=8):
    """Prompts over one shared 8-token prefix (two whole pages)."""
    rs = np.random.RandomState(seed)
    prefix = rs.randint(1, 127, (8,)).astype(np.int32)
    return [np.concatenate([prefix, rs.randint(1, 127, (tail,)).astype(np.int32)])
            for _ in range(tails)]


def pressure_submits():
    """Family A, a burst of family B wide enough to spill A's prefix out of
    the small pool, then A again (a restore on the hit); greedy and sampled."""
    a, b = family(1, 2), family(2, 3)
    return ([dict(prompt=a[0], max_new_tokens=8)]
            + [dict(prompt=p, max_new_tokens=8, arrival_block=4,
                    sampler=Sampler(temperature=1.1) if i == 1 else None)
               for i, p in enumerate(b)]
            + [dict(prompt=a[1], max_new_tokens=8, arrival_block=12,
                    sampler=Sampler(temperature=0.8))])


def streams(served):
    return {c.request_id: c.tokens.tolist() for c in served.completed}


def serve(lm, submits, **kw):
    engine = ServeEngine(lm, block_steps=K, rng=jax.random.key(42), **kw)
    for s in submits:
        engine.submit(**s)
    engine.run(max_blocks=300)
    return engine


def spilled_and_restored(dtype):
    big, submits = latent_lm(dtype), pressure_submits()
    engine = serve(latent_lm(dtype, SMALL_POOL), submits, host_tier_pages=TIER)
    stats = engine.session.paged.stats
    assert stats["tier_spilled_pages"] > 0 and stats["tier_restored_pages"] > 0
    assert stats["tier_hits"] > 0
    return streams(engine), streams(serve(big, submits))


def handed_off_and_adopted(dtype):
    big, submits = latent_lm(dtype), pressure_submits()
    router = DisaggRouter(big, 2, prefill_replicas=1, rng=jax.random.key(42), block_steps=K)
    for s in submits:
        router.submit(**s)
    router.run(max_blocks=300)
    assert router.stats["handoffs_adopted"] == len(submits)
    assert router.stats["handoffs_degraded"] == 0
    return streams(router), streams(serve(big, submits))


def corrupted_and_replayed(dtype):
    """The replay prefills the prompt and what was delivered, where the
    undisturbed run decoded it: the same tokens exactly in float32; in
    bfloat16 the expanded (prompt) and absorbed (decode) forms of the latent
    attention round apart and a near-tied argmax may part the streams, so
    there the oracle is a fresh engine asked to continue from the same
    tokens, which the replay must equal in any dtype."""
    big, prompt = latent_lm(dtype), family(3, 1)[0]
    engine = ServeEngine(big, block_steps=K, rng=jax.random.key(42))
    engine.submit(prompt, 10)
    engine.step_block()
    slot = next(i for i, r in enumerate(engine.slots) if r is not None)
    so_far = [int(t) for t in engine._out[slot]]
    engine.inject_page_corruption([engine.session.paged.slot_pages(slot)[0]])
    assert engine.stats["corrupt_page_replays"] == 1 and 0 < len(so_far) < 10
    engine.run()
    resumed = serve(big, [dict(prompt=np.concatenate([prompt, so_far]).astype(np.int32),
                               max_new_tokens=10 - len(so_far))])
    assert streams(engine)[0] == so_far + streams(resumed)[0]
    if dtype == "bfloat16":
        return streams(engine), streams(engine)
    return streams(engine), streams(serve(big, [dict(prompt=prompt, max_new_tokens=10)]))


DISTURBED = {"tier_spill_restore": spilled_and_restored, "handoff_adopt": handed_off_and_adopted,
             "corruption_replay": corrupted_and_replayed}


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("how", sorted(DISTURBED))
def test_latent_pages_through_page_io_give_the_undisturbed_streams(how, dtype):
    got, want = DISTURBED[how](dtype)
    assert got == want and all(len(tokens) >= 8 for tokens in want.values())


# ------------------------------------------------------------------- sizing

def by_hand(cfg, pages):
    """``(page bytes, state bytes)`` of a session's cache from the config's
    own leaf shapes: a page leaf is held once per layer that pages (every
    layer, or a hybrid's attention layers), a per-slot state leaf once per
    layer of the other kind."""
    cfg = dataclasses.replace(cfg, decode=True, page_size=PAGE, page_pool_pages=pages)
    rows = getattr(cfg, "slot_row_leaves", ())
    paging = cfg.layers_of("attention") if rows else cfg.num_layers
    page_bytes = state_bytes = 0
    for name, (shape, dtype) in cfg.kv_leaf_shapes(ROWS).items():
        nbytes = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
        if name in rows:
            state_bytes += nbytes * cfg.layers_of("mamba")
        else:
            page_bytes += nbytes * paging
    return page_bytes, state_bytes


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("kind", ["latent", "hybrid"])
def test_cache_bytes_are_the_leaf_shapes_counted_by_hand(kind, dtype):
    world()
    over = dict(dtype=DTYPES[dtype], param_dtype=DTYPES[dtype])
    cfg, cls = ((DeepseekV2Config(**LATENT, **over), DeepseekV2ForCausalLM) if kind == "latent"
                else (GraniteHybridConfig(**HYBRID, **over), GraniteHybridForCausalLM))
    shapes = jax.eval_shape(lambda: meta.unbox(cls(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    lm = CausalLM(cfg, shapes, cls, buckets=(8,), max_batch=ROWS, page_size=PAGE,
                  prefix_cache=kind == "latent")
    pages = lm.config.page_pool_pages
    assert pages == ROWS * (cfg.max_seq_len // PAGE) + ROWS
    page_bytes, state_bytes = by_hand(cfg, pages)
    sizes = lm.kv_cache_bytes()
    assert sizes["kv_bytes"] == sizes["kv_bytes_global"] == page_bytes > 0
    assert sizes.get("state_bytes", 0) == state_bytes and (state_bytes > 0) == (kind == "hybrid")
    assert lm.kv_page_bytes() == lm.kv_page_bytes_host() == page_bytes // pages
    item = jnp.dtype(DTYPES[dtype]).itemsize
    if kind == "latent":        # one leaf, [c_kv | k_rope] wide, every layer
        assert lm.kv_page_bytes() == 2 * PAGE * (16 + 4) * item
    else:                       # K and V, the two attention layers only
        assert lm.kv_page_bytes() == 2 * 2 * PAGE * 2 * 8 * item
    # the slab the pool competes with: the same leaves at max_batch x max_seq_len
    assert sizes["kv_slab_bytes"] == page_bytes * ROWS * cfg.max_seq_len // (pages * PAGE)
