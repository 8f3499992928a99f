"""Granite-4.0-H (``models/granite_hybrid.py``) against its plain reference:
tiny widths, float32, seeded weights, logits and not tokens.

40 layers become the one period ``m m m m m A m m m m`` twice over (20
layers: the scan over periods turns twice),
hidden 64, 4 heads of 16 (2 KV), Mamba-2 with 8 heads of 16, state 16, chunk 8.
The norm scales, ``D`` and the gate norm are shaken away from one so that a
scale applied on the wrong axis shows.

The tolerance: float32 against float32 under ``highest`` matmul precision.
The program takes the chunked (SSD) form over a prompt and the reference one
token a turn, so sums are taken in another order and nothing else differs:
logits within 1e-4 of the reference's largest. The right mathematics sits near
1e-6; each wrong one in ``test_wrong_mathematics_fails`` is over ten times past
the tolerance.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta
from jax.sharding import PartitionSpec

from benchmark.reference import granite_hybrid as reference
from neuronx_distributed_tpu.inference import ServeEngine, causal_lm
from neuronx_distributed_tpu.inference.partition import leaf_partition_spec
from neuronx_distributed_tpu.models import granite_hybrid
from neuronx_distributed_tpu.models.granite_hybrid import (
    GraniteHybridConfig,
    GraniteHybridForCausalLM,
    Mamba2Mixer,
    granite_4_0_h_micro,
    ssd_chunked,
)
from neuronx_distributed_tpu.models.llama import KVLayerView
from tests import tiny
from tests.tiny import distance, world

TOL = 1e-4
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=96, num_layers=20, num_heads=4,
            num_kv_heads=2, head_dim=16, layer_types=PERIOD * 2, mamba_n_heads=8,
            mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8, attention_multiplier=0.75,
            embedding_multiplier=12.0, logits_scaling=8.0, residual_multiplier=0.22,
            max_seq_len=64, dtype=jnp.float32, param_dtype=jnp.float32,
            use_flash_attention=False, remat_policy=None)
SIZES = {"rms_norm_eps": 1e-5, "layer_types": PERIOD * 2, "mamba_n_heads": 8, "mamba_d_head": 16,
         "mamba_d_state": 16, "attention_multiplier": 0.75, "embedding_multiplier": 12.0,
         "logits_scaling": 8.0, "residual_multiplier": 0.22, "tie_word_embeddings": True}
IDS = np.random.RandomState(0).randint(1, 256, (4, 40)).astype(np.int32)
STEPS = 5


full_forward = functools.partial(tiny.full_forward, GraniteHybridForCausalLM, ids=IDS)
serving_lm = functools.partial(tiny.serving_lm, GraniteHybridForCausalLM,
                               cfg=GraniteHybridConfig(**TINY), buckets=(16, 32), prefix_cache=False)


def shake(name, a):
    if name.endswith("['D']"):
        return a * (1.0 + 0.3 * tiny.noise(name, a))
    return tiny.shake_norms(name, a)


@pytest.fixture(scope="module")
def params():
    world()
    return tiny.make_params(GraniteHybridForCausalLM, GraniteHybridConfig(**TINY), IDS, shake)


def ref_logits(params, ids, sizes=SIZES):
    return np.asarray(reference.forward(params, jnp.asarray(ids), sizes))


@pytest.fixture(scope="module")
def want(params):
    return ref_logits(params, IDS)


@pytest.fixture(scope="module")
def lm(params):
    """The serving lm of every test that plants nothing in it: its programs
    (an insert a bucket, the step, the fused block) compile once a process."""
    world()
    return serving_lm(params)


def insert_then_step(lm, lens, steps=STEPS, slots=None):
    """Logits ``(steps + 1, rows, vocab)``: the insert's, then ``steps``
    teacher-forced ``lm.step``s over IDS's own continuation."""
    rows = len(lens)
    slots = np.arange(rows) if slots is None else np.asarray(slots)
    lens = np.asarray(lens)
    session = lm.start_session()
    with jax.default_matmul_precision("highest"):
        got = [np.asarray(lm.insert(session, slots, tiny.padded(IDS, range(rows), lens), lengths=lens,
                                    reserve_tokens=steps + 1))]
        for t in range(steps):
            tok = np.zeros((lm.max_batch,), np.int32)
            tok[slots] = IDS[np.arange(rows), lens + t]
            got.append(np.asarray(lm.step(session, tok))[slots])
    return np.stack(got)


# --------------------------------------------------------------- the forward

def test_preset_is_the_published_configuration():
    cfg = granite_4_0_h_micro()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads) == (40, 2048, 32, 8)
    assert (cfg.period, cfg.layers_of("mamba"), cfg.layers_of("attention")) == (10, 36, 4)
    assert (cfg.d_inner, cfg.conv_dim, cfg.head_dim_) == (4096, 4352, 64)
    assert not cfg.use_rope and cfg.attention_multiplier == 1 / 64
    assert [i for i, t in enumerate(cfg.layer_types) if t == "attention"] == [5, 15, 25, 35]


def test_full_forward_equals_the_reference(params, want):
    """(a) the chunked form over 40 tokens (five chunks of 8) against the
    reference's token-by-token recurrence."""
    world()
    assert distance(full_forward(GraniteHybridConfig(**TINY), params), want) <= TOL


@pytest.mark.parametrize("s", [5, 8, 9, 23])
def test_the_chunked_scan_equals_the_recurrence(s):
    """One chunk not filled, one filled, one more than a chunk, three with a
    ragged last: against a loop over tokens, state carried in and out."""
    rng = np.random.RandomState(s)
    b, h, p, n = 2, 3, 4, 5
    x, B, C = (rng.randn(b, s, *d).astype(np.float32) for d in ((h, p), (n,), (n,)))
    dt = np.abs(rng.randn(b, s, h)).astype(np.float32) * 0.3
    A = -np.abs(rng.randn(h)).astype(np.float32)
    S = rng.randn(b, h, p, n).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        y, S_out = ssd_chunked(*(jnp.asarray(a) for a in (x, dt, dt * A, B, C, S)), chunk=8)
    ys = []
    for t in range(s):
        S = (np.exp(dt[:, t] * A)[..., None, None] * S
             + (dt[:, t, :, None] * x[:, t])[..., None] * B[:, t, None, None, :])
        ys.append(np.einsum("bhpn,bn->bhp", S, C[:, t]))
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(S_out), S, rtol=2e-5, atol=2e-5)


def test_only_a_one_token_step_with_a_state_runs_the_kernel(monkeypatch):
    """The mixer alone, so that no ``nn.jit`` trace kept from another test
    hides a call: one token a row on a layer's rows of the stacked leaves goes
    through ``kernels/ssm_step.py`` once, gives what the chunked form gives
    without a state that starts from the same rows, and leaves the other
    layers' rows alone; a prompt on the same leaves and any forward pass that
    keeps nothing do not call it."""
    world()
    cfg = GraniteHybridConfig(**TINY)
    calls = []

    def counted(*args, **kw):
        calls.append(args[0].shape)
        return ssm_step(*args, **kw)

    ssm_step = granite_hybrid.ssm_step
    monkeypatch.setattr(granite_hybrid, "ssm_step", counted)
    mixer = Mamba2Mixer(cfg)
    b, layers, layer = 3, 4, 2
    rng = np.random.RandomState(5)
    u = jnp.asarray(rng.randn(b, 6, cfg.hidden_size), jnp.float32)
    params = meta.unbox(mixer.init(jax.random.key(2), u))
    assert not calls                                        # init: a prompt, no state

    def leaves():
        return {name: jnp.asarray(rng.randn(layers, *shape), dtype)
                for name, (shape, dtype) in cfg.kv_leaf_shapes(b).items() if name in cfg.slot_row_leaves}

    def apply(u, state=None, live=None):
        view = None if state is None else KVLayerView(jnp.int32(layer), state)
        out = mixer.apply(params, u, view, live)
        return out, None if view is None else view.leaves

    before = leaves()
    live = jnp.asarray([[True], [False], [True]])
    out, after = apply(u[:, :1], before, live)
    assert calls == [(layers * b, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state)]
    for name in before:
        others = np.arange(layers) != layer
        np.testing.assert_array_equal(np.asarray(after[name])[others], np.asarray(before[name])[others])
        np.testing.assert_array_equal(np.asarray(after[name])[layer, 1], np.asarray(before[name])[layer, 1])
        assert not np.array_equal(np.asarray(after[name])[layer, 0], np.asarray(before[name])[layer, 0])
    del calls[:]
    apply(u, leaves(), jnp.ones((b, 6), bool))              # a prompt on a state: the chunked form
    apply(u)                                                # a forward pass, nothing kept
    apply(u[:, :1])                                         # one token, nothing kept
    assert not calls
    # one token from zeros, with the state and without: the same output
    zeros = jax.tree.map(jnp.zeros_like, before)
    np.testing.assert_allclose(np.asarray(apply(u[:, :1], zeros)[0]), np.asarray(apply(u[:, :1])[0]),
                               rtol=1e-5, atol=1e-6)
    assert len(calls) == 1


WRONG_FORWARD = {
    "no_conv_bias": dict(mamba_conv_bias=False),
    "one_over_sqrt_d": dict(attention_multiplier=None),
    "rotation_applied": dict(position_embedding_type="rope"),
    "residual_multiplier_dropped": dict(residual_multiplier=1.0),
    "embedding_multiplier_dropped": dict(embedding_multiplier=1.0),
}


@pytest.mark.parametrize("wrong", sorted(WRONG_FORWARD))
def test_wrong_mathematics_fails(params, want, wrong):
    """(h) each departure from the published equations moves the logits far
    past the tolerance."""
    world()
    cfg = GraniteHybridConfig(**dict(TINY, **WRONG_FORWARD[wrong]))
    assert distance(full_forward(cfg, params), want) > 10 * TOL


def test_a_lower_precision_fails(params, want):
    """The control: the reference with every weight rounded to bf16 lands far
    outside the float32 tolerance."""
    rounded = jax.tree.map(lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32), params)
    assert distance(ref_logits(rounded, IDS), want) > 10 * TOL


# ------------------------------------------------------------- the serving path

# (c) a prompt shorter than its bucket beside one that fills it (32, and 16 of
# the smaller bucket); (d) lengths on both sides of a multiple of the chunk 8
LENGTHS = {"short_beside_full": [19, 32, 7], "fills_the_small_bucket": [16, 16],
           "around_a_chunk": [15, 16, 17], "around_three_chunks": [23, 24, 25, 9]}


@pytest.mark.parametrize("case", sorted(LENGTHS))
def test_insert_and_decode_equal_the_reference(lm, want, case):
    """(b) prefill then decode through ``lm.insert`` / ``lm.step``: the state
    after a padded bucket is the state after the row's last real token, so
    every decoded position equals the reference's full forward."""
    world()
    lens = LENGTHS[case]
    assert distance(insert_then_step(lm, lens), tiny.at_cached(want, lens, STEPS)) <= TOL


def run_engine(lm, prompts, budget, **kw):
    engine = ServeEngine(lm, block_steps=4, rng=jax.random.key(0), **kw)
    with jax.default_matmul_precision("highest"):
        ids = [engine.submit(p, max_new_tokens=budget, arrival_block=0) for p in prompts]
        while engine.step_block():
            pass
    assert not engine.rejected
    done = {c.request_id: np.asarray(c.tokens) for c in engine.completed}
    return engine, [done[i] for i in ids]


def greedy_by_the_reference(params, prompt, tokens):
    """Whether ``tokens`` is the greedy continuation of ``prompt`` under the
    reference's full forward over both."""
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    logits = ref_logits(params, seq[None])[0]
    return logits[len(prompt) - 1: len(seq) - 1].argmax(-1), logits


@pytest.mark.parametrize("loop", ["fused", "stepwise", "async"])
def test_the_engines_block_decodes_what_the_reference_does(params, lm, loop):
    """(b) through ``ServeEngine``: six requests over four slots, so slots
    are reused and inserts land in a running batch; every token is the
    reference's argmax over the whole sequence so far."""
    world()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 256, (n,)).astype(np.int32) for n in (19, 32, 7, 16, 25, 9)]
    kw = {"fused": dict(), "stepwise": dict(fused=False), "async": dict(async_loop=True)}[loop]
    engine, tokens = run_engine(lm, prompts, 9, **kw)
    for prompt, got in zip(prompts, tokens):
        assert len(got) == 9
        np.testing.assert_array_equal(got, greedy_by_the_reference(params, prompt, got)[0])
    stats = engine.stats
    assert stats["ssm_scan_tokens"] == sum(len(p) for p in prompts)
    assert stats["ssm_scan_positions"] >= stats["ssm_scan_tokens"]


def test_an_insert_into_a_running_batch_leaves_the_other_rows_alone(lm):
    """(e) rows 0 and 1 decode; after two steps a third request is inserted
    into slot 2. Their logits are bit-identical to a run without it."""
    world()
    lens = np.asarray([19, 12])

    def run(with_insert):
        session = lm.start_session()
        got = [np.asarray(lm.insert(session, np.arange(2), tiny.padded(IDS, range(2), lens), lengths=lens,
                                    reserve_tokens=STEPS + 1))]
        for t in range(STEPS):
            if with_insert and t == 2:
                lm.insert(session, np.asarray([2]), IDS[3:4, :21], lengths=np.asarray([21]),
                          reserve_tokens=STEPS + 1)
            tok = np.zeros((lm.max_batch,), np.int32)
            tok[:2] = IDS[np.arange(2), lens + t]
            got.append(np.asarray(lm.step(session, tok))[:2])
        return np.stack(got)

    np.testing.assert_array_equal(run(True), run(False))


def test_a_reused_slot_starts_from_zero(lm, want):
    """(f) slot 0 serves one request, is retired, and serves another: the
    second sees no trace of the first."""
    world()
    session = lm.start_session()
    with jax.default_matmul_precision("highest"):
        lm.insert(session, np.asarray([0]), IDS[3:4, :30], lengths=np.asarray([30]),
                  reserve_tokens=4)
        for _ in range(3):
            lm.step(session, np.full((4,), 7, np.int32))
        lm.retire(session, [0])
        n = 19
        got = [np.asarray(lm.insert(session, np.asarray([0]), IDS[:1, :n], lengths=np.asarray([n]),
                                    reserve_tokens=STEPS + 1))]
        for t in range(STEPS):
            tok = np.zeros((4,), np.int32)
            tok[0] = IDS[0, n + t]
            got.append(np.asarray(lm.step(session, tok))[:1])
    assert distance(np.stack(got), tiny.at_cached(want[:1], [n], STEPS)) <= TOL


def test_a_chunked_extend_equals_one_insert(params, lm):
    """(g) a prompt admitted in chunks of 16 continues from the state its last
    chunk left: the same tokens as one insert, and the reference's."""
    world()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 256, (n,)).astype(np.int32) for n in (30, 13, 32)]
    _, whole = run_engine(lm, prompts, 6)
    engine, chunked = run_engine(lm, prompts, 6, prefill_chunk_tokens=16)
    assert engine.stats["chunk_program_calls"] > 0
    for prompt, a, b in zip(prompts, whole, chunked):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, greedy_by_the_reference(params, prompt, a)[0])


WRONG_SERVING = ["state_not_reset", "padding_scanned"]


@pytest.mark.parametrize("wrong", WRONG_SERVING)
def test_wrong_serving_fails(params, want, wrong, monkeypatch):
    """(h) the serving path's own two duties, each switched off: a reused slot
    that keeps its last tenant's state, and a recurrence run over the bucket's
    padding. Both show in the decoded positions, far past the tolerance."""
    world()
    lm = serving_lm(params)
    if wrong == "padding_scanned":
        lm.wants_live = False
        assert distance(insert_then_step(lm, [19, 32, 7]), tiny.at_cached(want, [19, 32, 7], STEPS)) > 10 * TOL
        return
    monkeypatch.setattr(causal_lm, "_state_rows", lambda leaf, slots, starts: leaf[:, slots])
    session = lm.start_session()
    with jax.default_matmul_precision("highest"):
        lm.insert(session, np.asarray([0]), IDS[3:4, :30], lengths=np.asarray([30]),
                  reserve_tokens=4)
        lm.retire(session, [0])
        # a short prompt: seeded decays forget a tenant within tens of tokens
        got = lm.insert(session, np.asarray([0]), IDS[:1, :3], lengths=np.asarray([3]),
                        reserve_tokens=4)
    assert distance(np.asarray(got), want[:1, 2]) > 10 * TOL


# --------------------------------------------------------------- the cache

def test_the_leaves_are_stacked_by_kind_and_counted_apart(params):
    world()
    lm = serving_lm(params)
    shapes = {jax.tree_util.keystr(p).split("']['")[-1].strip("']"): leaf.shape
              for p, leaf in jax.tree_util.tree_flatten_with_path(lm._cache_avals())[0]}
    pages = lm.config.page_pool_pages
    assert shapes["cached_key"] == shapes["cached_value"] == (2, pages, 8, 2, 16)
    assert shapes["ssm_state"] == (18, 4, 8, 16, 16)
    assert shapes["conv_state"] == (18, 4, 3, 128 + 32)
    assert shapes["cache_index"] == (2, 4) and shapes["block_table"] == (2, 4, 8)
    sizes = lm.kv_cache_bytes()
    assert sizes["kv_bytes"] == 2 * 2 * pages * 8 * 2 * 16 * 4
    assert sizes["state_bytes"] == 18 * 4 * (8 * 16 * 16 + 3 * 160) * 4
    assert lm.slot_rows == ("ssm_state", "conv_state") and lm.wants_live


REFUSED = {
    "prefix_cache": lambda p: serving_lm(p, prefix_cache=True),
    "the_slab": lambda p: serving_lm(p, page_size=None),
    "lora": lambda p: serving_lm(p, lora_rank=4, lora_slots=2),
    "int8_pages": lambda p: serving_lm(p, page_dtype="int8"),
    "handoff_prefill": lambda p: ServeEngine(serving_lm(p), role="prefill"),
    "handoff_decode": lambda p: ServeEngine(serving_lm(p), role="decode"),
    "host_tier": lambda p: ServeEngine(serving_lm(p), host_tier_pages=4),
    "parking": lambda p: ServeEngine(serving_lm(p), park_idle_blocks=2, park_dir="/nonexistent"),
    "page_corruption": lambda p: ServeEngine(serving_lm(p)).inject_page_corruption([1]),
    "generate": lambda p: serving_lm(p).generate(IDS[:1, :8], 4),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_moves_a_cache_by_pages_is_refused(params, feature):
    """(i) everything that reuses or moves a slot's cache by pages, or rewinds
    it by position, raises instead of leaving the state behind."""
    world()
    with pytest.raises(ValueError):
        REFUSED[feature](params)


def test_the_state_leaves_are_not_served_across_tp(params, monkeypatch):
    """``partition.py`` has no rule for them (replicated, as any leaf it does
    not name); ``CausalLM`` refuses the model where that would be wrong."""
    assert leaf_partition_spec("['model']['ssm_state']", (36, 16, 64, 64, 128), 4) == \
        leaf_partition_spec("['model']['conv_state']", (36, 16, 3, 4352), 4) == PartitionSpec()
    world()
    monkeypatch.setattr(causal_lm, "tp_degree", lambda: 4)
    with pytest.raises(ValueError, match="tensor parallelism"):
        serving_lm(params)


@pytest.mark.parametrize("bad", [dict(layer_types=["mamba"] * 19), dict(mamba_n_groups=2),
                                 dict(mamba_n_heads=7), dict(position_embedding_type="alibi"),
                                 dict(layer_types=["conv"] * 20)],
                         ids=["length", "groups", "widths", "positions", "kinds"])
def test_a_configuration_the_model_cannot_run_is_refused(bad):
    with pytest.raises(ValueError):
        GraniteHybridConfig(**dict(TINY, **bad))
