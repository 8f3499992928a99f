"""The layout ``CausalLM`` HOLDS its weights in (ISSUE 53), on the CPU.

The first program lowered that runs the one-token step asks the compiler, by
``Layout.AUTO`` on its ``params`` argument, how it reads each weight leaf;
``CausalLM`` re-lays the leaves that lie otherwise, once, and lowers every
program with those formats fixed on ``params``. The CPU's compiler keeps
every default, so here (a) nothing moves and the tokens are the parent
commit's, bit for bit; (b) a NON-default layout handed to the same helper the
asking path ends in (``CausalLM._hold``) goes through every program, with
equal logits, and a compiled program refuses the leaf as it was loaded;
(c) under a two-device ``tp`` mesh the re-laid leaf keeps its sharding.
What the TPU's compiler answers, and that the fused block then copies no
weight at its entry, is ``tests/test_aot_tpu_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from neuronx_distributed_tpu.inference import CausalLM, Sampler, ServeEngine
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.parallel import mesh as psm
from neuronx_distributed_tpu.trainer import (
    initialize_parallel_model,
    neuronx_distributed_config,
)

TINY = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, kv_size_multiplier=1, max_seq_len=64,
    dtype=jnp.float32, use_flash_attention=False, remat_policy=None,
)
K = 4
Q = "['q_kernel']"
HEAD_MAJOR = (0, 2, 1, 3)         # (layers, hidden, heads, head_dim) with the heads outside hidden


def _params(seed=0, committed=False):
    cfg = LlamaConfig(**TINY)
    params = meta.unbox(LlamaForCausalLM(cfg).init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))["params"]
    if committed:
        params = jax.device_put(params, SingleDeviceSharding(jax.devices()[0]))
    return cfg, params


def _lm(params=None, **kw):
    cfg, loaded = _params()
    return CausalLM(cfg, loaded if params is None else params, LlamaForCausalLM,
                    buckets=(8, 16), max_batch=3, **kw)


def _prompts(n, s=8, seed=2):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n, s), 1, 127))


def _streams(lm, fused=True):
    """An exactness case of ``tests/test_serving_engine.py``: staggered
    arrivals, a greedy row beside two sampled ones in one pool."""
    p = _prompts(3, seed=5)
    engine = ServeEngine(lm, block_steps=K, fused=fused, rng=jax.random.key(42))
    ids = [engine.submit(prompt=p[0], max_new_tokens=9),
           engine.submit(prompt=p[1], max_new_tokens=7, sampler=Sampler(temperature=0.8),
                         arrival_block=1),
           engine.submit(prompt=p[2], max_new_tokens=5, sampler=Sampler(temperature=1.3),
                         arrival_block=2)]
    done = {c.request_id: c.tokens.tolist() for c in engine.run()}
    return engine, [done[i] for i in ids]


def _head_major(params, leaf_ends=Q):
    """A format tree as the asking compile reports one: a ``Format`` for the
    leaves named, None (as the leaf is) for the rest."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: Format(Layout(major_to_minor=HEAD_MAJOR), leaf.sharding)
        if jax.tree_util.keystr(path).endswith(leaf_ends) else None, params)


def _leaf(tree, ends=Q):
    return next(leaf for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
                if jax.tree_util.keystr(path).endswith(ends))


# the parent commit's (7b79366) tokens of ``_streams``, taken on its tree
PARENT_STREAMS = [[52, 31, 106, 52, 31, 22, 14, 31, 72],
                  [124, 50, 55, 50, 55, 16, 69],
                  [21, 105, 45, 5, 64]]


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_the_cpu_keeps_every_default_and_the_tokens_are_the_parents(paged):
    lm = _lm(page_size=4 if paged else None)
    assert not lm._formats_settled
    loaded = jax.tree_util.tree_leaves(lm.params)
    engine, streams = _streams(lm)
    assert lm._formats_settled and lm._param_formats is None
    assert (lm.param_relaid_leaves, lm.param_relaid_bytes) == (0, 0)
    assert engine.stats["param_relaid_leaves"] == engine.stats["param_relaid_bytes"] == 0
    # not one leaf was touched: the tree holds the arrays it was given
    assert all(a is b for a, b in zip(loaded, jax.tree_util.tree_leaves(lm.params)))
    assert streams == PARENT_STREAMS
    assert _streams(lm, fused=False)[1] == streams


def test_a_format_that_is_not_the_default_goes_through_every_program():
    cfg, params = _params(committed=True)
    plain = _lm(params)
    held = _lm(params)
    held._hold(_head_major(params))          # what ``_ask_formats`` does with the compiler's answer
    q = _leaf(held.params)
    assert q.format.layout.major_to_minor == HEAD_MAJOR
    assert _leaf(params).format.layout.major_to_minor == (0, 1, 2, 3)     # the caller's is as it was
    assert (held.param_relaid_leaves, held.param_relaid_bytes) == (1, q.size * 4)
    assert _leaf(held._param_formats).layout.major_to_minor == HEAD_MAJOR
    assert sum(f is not None for f in jax.tree_util.tree_leaves(
        held._param_formats, is_leaf=lambda f: f is None)) == 1
    # decode, the prefills, a fused generate block, the session block, a slab
    # insert, a chunk extend: each lowered with the format fixed, each right
    prompts = _prompts(3)
    want = plain.generate(prompts, max_new_tokens=6, fused_chunk=3)
    got = held.generate(prompts, max_new_tokens=6, fused_chunk=3)
    np.testing.assert_array_equal(got.tokens, want.tokens)
    for lm in (plain, held):
        lm.session = lm.start_session()
    logits = [np.asarray(lm.insert(lm.session, np.arange(2), prompts[:2]))
              for lm in (plain, held)]
    np.testing.assert_allclose(logits[1], logits[0], rtol=1e-6, atol=1e-6)
    steps = [np.asarray(lm.step(lm.session, np.array([5, 6, 7]))) for lm in (plain, held)]
    np.testing.assert_allclose(steps[1], steps[0], rtol=1e-6, atol=1e-6)
    chunks = [np.asarray(lm.extend(lm.session, np.array([2]), prompts[2:3, :4],
                                   np.array([4]), np.array([0]))) for lm in (plain, held)]
    np.testing.assert_allclose(chunks[1], chunks[0], rtol=1e-6, atol=1e-6)
    engine, streams = _streams(held)
    assert streams == _streams(plain)[1]
    assert engine.stats["param_relaid_leaves"] == 1
    for program in (held._decode, *held._prefill.values(), *held._decode_fused.values(),
                    *held._session_fused.values(), *held._slab_insert.values(),
                    *held._chunk_extend.values()):
        assert _leaf(program.input_formats[0][0]).layout.major_to_minor == HEAD_MAJOR
    # the leaf as it was loaded is refused, not copied
    with pytest.raises(ValueError, match="layout"):
        held._decode(params, held.session.cache, jnp.zeros((3, 1), jnp.int32))
    # new weights take the held formats on the way in
    _, other = _params(seed=1, committed=True)
    held.params = other
    assert _leaf(held.params).format.layout.major_to_minor == HEAD_MAJOR
    np.testing.assert_array_equal(np.asarray(_leaf(held.params)), np.asarray(_leaf(other)))
    held.step(held.session, np.array([5, 6, 7]))


def test_a_paged_lm_takes_the_format_in_its_inserts_too():
    cfg, params = _params(committed=True)
    plain, held = _lm(params, page_size=4), _lm(params, page_size=4)
    held._hold(_head_major(params, ("['q_kernel']", "['k_kernel']", "['v_kernel']")))
    assert held.param_relaid_leaves == 3
    engine, streams = _streams(held)
    assert streams == _streams(plain)[1] == PARENT_STREAMS
    assert held._paged_insert and all(
        _leaf(program.input_formats[0][0], "['v_kernel']").layout.major_to_minor == HEAD_MAJOR
        for program in (*held._paged_insert.values(), *held._session_fused.values()))


def test_a_relaid_leaf_keeps_its_sharding_under_tp():
    psm.initialize_model_parallel(tensor_model_parallel_size=2)
    cfg = LlamaConfig(**TINY)
    model = initialize_parallel_model(
        neuronx_distributed_config(tensor_parallel_size=2),
        lambda: LlamaForCausalLM(cfg), jnp.zeros((1, 8), jnp.int32))

    def lm():
        return CausalLM(cfg, model.params, LlamaForCausalLM, buckets=(8, 16), max_batch=3,
                        page_size=4)

    plain, held = lm(), lm()
    before = _leaf(model.params)
    assert len(before.sharding.device_set) > 1 and not before.sharding.is_fully_replicated
    held._hold(_head_major(model.params))
    after = _leaf(held.params)
    assert after.format.layout.major_to_minor == HEAD_MAJOR
    assert after.sharding == before.sharding
    assert [s.data.shape for s in after.addressable_shards] == \
        [s.data.shape for s in before.addressable_shards]
    np.testing.assert_array_equal(np.asarray(after), np.asarray(before))
    assert _streams(held)[1] == _streams(plain)[1]
    assert _leaf(held._session_fused[next(iter(held._session_fused))]
                 .input_formats[0][0]).sharding == before.sharding
