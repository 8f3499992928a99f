"""The KV pools are the layer scan's CARRY: what the compiled programs and the
cache tree look like (ISSUE 27).

As the scan's scanned input and output (``variable_axes={"cache": 0}``, the
form this replaces) every layer sliced its pool out of the stacked leaf and
wrote all of it back, and the fused decode's step loop copied the stack between
the two: time that followed the pages HELD, not the tokens live. Here, for a
tiny paged Llama, Mixtral and OLMoE whose pool is made large against everything
else, the fused session decode and the paged insert must

(a) alias every pool leaf from argument to result (donation honoured),
(b) hold temporaries well under ONE pool leaf,
(c) contain no value of a per-layer pool's shape at all.

The xs/ys form read 2.0 / 2.68 leaves / 4 such values on the same programs.
A model without a ``cache`` collection carries nothing more than before: the
GPT-NeoX train step lowers to the text of a scan that carries ``x`` alone.
The cache tree keeps its leaf names, shapes, dtypes and shardings; only the
pool leaves' path prefix moved (a golden listing).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.sharding import PartitionSpec as PS

from neuronx_distributed_tpu.inference import CausalLM
from neuronx_distributed_tpu.inference.sampling import SlotSampler
from neuronx_distributed_tpu.models import llama
from neuronx_distributed_tpu.models.gpt_neox import GPTNeoXConfig, GPTNeoXForCausalLM
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
from neuronx_distributed_tpu.models.olmoe import OlmoeConfig, OlmoeForCausalLM
from neuronx_distributed_tpu.parallel import mesh as psm
from neuronx_distributed_tpu.trainer import (
    initialize_parallel_model,
    neuronx_distributed_config,
)
from tests import tiny

L, NPAGES, PAGE, N_KV, HD = 3, 2048, 4, 2, 8
TINY = dict(
    vocab_size=64, hidden_size=16, intermediate_size=32, num_layers=L,
    num_heads=2, num_kv_heads=N_KV, max_seq_len=32, dtype=jnp.float32,
    use_flash_attention=False, remat_policy=None,
)
MODELS = {
    "llama": (LlamaConfig, LlamaForCausalLM, {}),
    "mixtral": (MixtralConfig, MixtralForCausalLM, dict(num_experts=2, top_k=1)),
    "olmoe": (OlmoeConfig, OlmoeForCausalLM, dict(num_experts=4, top_k=2)),
}
PROGRAMS = {
    "session_fused": lambda lm: lm.compile_session_decode_fused(2, SlotSampler(), 0),
    "paged_insert": lambda lm: lm._paged_insert_programs(1, 8),
}
POOL_LEAF_BYTES = L * NPAGES * PAGE * N_KV * HD * 4


def _lm(model):
    config_cls, model_cls, over = MODELS[model]
    cfg = config_cls(**{**TINY, **over})
    return tiny.built(("kv_carry_structure", model), lambda: CausalLM(
        cfg, tiny.make_params(model_cls, cfg, seed=0), model_cls, buckets=(8,), max_batch=2,
        page_size=PAGE, page_pool_pages=NPAGES))


@pytest.mark.parametrize("program", sorted(PROGRAMS))
@pytest.mark.parametrize("model", sorted(MODELS))
def test_pool_is_updated_in_place(model, program):
    compiled = PROGRAMS[program](_lm(model))
    memory = compiled.memory_analysis()
    # (a) both pool leaves go from argument to result in one buffer
    assert memory.alias_size_in_bytes >= 2 * POOL_LEAF_BYTES
    assert "input_output_alias" in compiled.as_text()
    # (b) no copy of a leaf, and no layer's share of one (a third), is held
    assert memory.temp_size_in_bytes < POOL_LEAF_BYTES / (2 * L), (
        memory.temp_size_in_bytes / POOL_LEAF_BYTES)
    # (c) no layer ever holds a pool of its own: nothing in the program has
    # the shape the scan's xs/ys slices had
    per_layer = re.findall(rf"\[(?:1,)?{NPAGES},{PAGE},{N_KV},{HD}\]", compiled.as_text())
    assert not per_layer, per_layer[:4]


# --------------------------------------------------- no cache, nothing carried

class _StepCarryingX(nn.Module):
    """The scan body as it was before the pools rode the carry."""

    config: LlamaConfig
    layer_cls: type

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.config
        cls = nn.remat(self.layer_cls, prevent_cse=False,
                       policy=llama._remat_policy(cfg.remat_policy))
        return cls(cfg, name="block")(x, rope), None


class _ModelCarryingX(llama.LlamaModel):
    def setup(self):
        cfg = self.config
        self.embed = llama.ParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, shard_over="vocab",
            dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        self.layers = nn.scan(
            _StepCarryingX,
            variable_axes={"params": 0, "cache": 0, "losses": 0,
                           "adapters": 0, "moe_stats": 0},
            split_rngs={"params": True}, length=cfg.num_layers,
            in_axes=nn.broadcast,
            metadata_params={nn.meta.PARTITION_NAME: None},
        )(cfg, self.layer_cls)
        self.final_norm = cfg.make_norm()

    def __call__(self, input_ids):
        cfg = self.config
        x = self.embed(input_ids)
        rope = llama.rotary_embedding(
            jnp.arange(input_ids.shape[1], dtype=jnp.int32), cfg.rope_dims,
            cfg.rope_theta, dtype=x.dtype, scaling=cfg.rope_scaling)
        x = llama.constrain(x, llama.ACT_SP if cfg.sequence_parallel else llama.ACT_FULL)
        x, _ = self.layers(x, rope)
        return self.final_norm(x)


class _NeoXCarryingX(GPTNeoXForCausalLM):
    def setup(self):
        cfg = self.config
        self.model = _ModelCarryingX(cfg, self.layer_cls)
        self.lm_head = llama.ColumnParallelLinear(
            cfg.vocab_size, use_bias=False, gather_output=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype)


def test_gpt_neox_train_step_carries_nothing_new():
    """TP=4 + SP with "attention" remat, the construction of the training
    cell: the loss-and-gradient program of the model as it is lowers to the
    very text of the form whose scan carries ``x`` alone."""
    cfg = GPTNeoXConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=4, max_seq_len=64, dtype=jnp.float32,
        use_flash_attention=False, remat_policy="attention", rotary_pct=0.25,
        sequence_parallel=True)
    nxd = neuronx_distributed_config(tensor_parallel_size=4, sequence_parallel=True)
    ids = jnp.asarray(np.random.RandomState(0).randint(1, 255, (4, 64)), jnp.int32)
    texts = {}
    for name, model_cls in (("now", GPTNeoXForCausalLM), ("carrying_x", _NeoXCarryingX)):
        model = initialize_parallel_model(nxd, lambda: model_cls(cfg), ids)

        def loss(params):
            return model.module.apply({"params": params}, ids, ids,
                                      method=model_cls.loss)

        texts[name] = jax.jit(jax.value_and_grad(loss)).lower(model.params).as_text()
    assert "cache" not in texts["now"]
    assert texts["now"] == texts["carrying_x"]


# ------------------------------------------------------------------ the tree

SMALL = "['model']['layers']['block']['attention']"
GOLDEN = {
    # paged, fp pages: (shape, dtype, spec at tp=2)
    "paged": {
        "['model']['cached_key']": ((2, 9, 4, 2, 8), "float32", PS(None, None, None, "tp", None)),
        "['model']['cached_value']": ((2, 9, 4, 2, 8), "float32", PS(None, None, None, "tp", None)),
        SMALL + "['block_table']": ((2, 3, 8), "int32", PS()),
        SMALL + "['cache_index']": ((2, 3), "int32", PS()),
    },
    "int8": {
        "['model']['cached_key']": ((2, 9, 4, 2, 8), "int8", PS(None, None, None, "tp", None)),
        "['model']['cached_key_scale']": ((2, 9, 1, 2, 1), "float32", PS(None, None, None, "tp", None)),
        "['model']['cached_value']": ((2, 9, 4, 2, 8), "int8", PS(None, None, None, "tp", None)),
        "['model']['cached_value_scale']": ((2, 9, 1, 2, 1), "float32", PS(None, None, None, "tp", None)),
        SMALL + "['block_table']": ((2, 3, 8), "int32", PS()),
        SMALL + "['cache_index']": ((2, 3), "int32", PS()),
    },
    "slab": {
        "['model']['cached_key']": ((2, 3, 32, 2, 8), "float32", PS(None, None, None, "tp", None)),
        "['model']['cached_value']": ((2, 3, 32, 2, 8), "float32", PS(None, None, None, "tp", None)),
        SMALL + "['cache_index']": ((2, 3), "int32", PS()),
    },
}
TREE_KW = {"paged": dict(page_size=4, page_pool_pages=9),
           "int8": dict(page_size=4, page_pool_pages=9, page_dtype="int8"),
           "slab": {}}


@pytest.mark.parametrize("kind", sorted(GOLDEN))
def test_cache_tree_is_the_parents_but_for_the_pool_prefix(kind):
    """Names, shapes, dtypes and ``NamedSharding`` of every cache leaf under
    a TP=2 mesh. The four K/V leaves sat beside ``cache_index`` (same names,
    same shapes, same specs) when the scan stacked them; page IO, handoff
    framing and the conversation tier find them by suffix."""
    psm.initialize_model_parallel(tensor_model_parallel_size=2)
    cfg = LlamaConfig(**{**TINY, "num_layers": 2})
    nxd = neuronx_distributed_config(tensor_parallel_size=2)
    model = initialize_parallel_model(nxd, lambda: LlamaForCausalLM(cfg),
                                      jnp.zeros((1, 8), jnp.int32))
    lm = CausalLM(cfg, model.params, LlamaForCausalLM, buckets=(8,), max_batch=3,
                  **TREE_KW[kind])
    avals = lm._cache_avals()        # what every cache-carrying program is lowered on
    got = {jax.tree_util.keystr(path): (leaf.shape, str(leaf.dtype), leaf.sharding.spec)
           for path, leaf in jax.tree_util.tree_flatten_with_path(avals)[0]}
    assert got == GOLDEN[kind]
    for leaf in jax.tree.leaves(avals):
        assert leaf.sharding.mesh == psm.get_mesh()


def test_pages_keyed_by_the_old_prefix_are_written_back():
    """Page payloads (host tier, handoff, parked conversations) are keyed by
    the leaf's whole path: one that a build before the move wrote, with the
    K/V leaves beside ``cache_index``, must still find its leaves."""
    from neuronx_distributed_tpu.inference import ServeEngine

    lm = _lm("llama")
    engine = ServeEngine(lm, block_steps=2)
    pools = {"cached_key", "cached_value"}
    engine.session.cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (jnp.arange(leaf.size, dtype=leaf.dtype).reshape(leaf.shape)
                            if path[-1].key in pools else leaf), engine.session.cache)
    pages = [3, 5]
    now = engine._read_pages_bytes(pages)
    assert set(now[0]) == {f"['model']['{name}']" for name in pools}
    old = [{SMALL + key[len("['model']"):]: value for key, value in page.items()}
           for page in now]
    engine._corrupt_page_bytes(pages)
    assert not np.array_equal(engine._read_pages_bytes(pages)[0]["['model']['cached_key']"],
                              now[0]["['model']['cached_key']"])
    engine._write_pages_bytes(pages, old)
    engine._corrupt_page_bytes(pages[1:])
    engine._write_page_bytes(pages[1], old[1])
    for got, want in zip(engine._read_pages_bytes(pages), now):
        for key in want:
            np.testing.assert_array_equal(got[key], want[key])
