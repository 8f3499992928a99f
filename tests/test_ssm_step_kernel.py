"""``kernels/ssm_step.py`` under the Pallas interpreter against the plain
``jnp`` recurrence, float32.

The leaf holds the rows of ``LAYERS`` Mamba layers; a call steps the ``b`` rows
of one of them. What the kernel owes: ``y`` and the stepped rows equal to the
recurrence's to 1e-6 (the expressions are the same; only a fused multiply-add
or the order of the sum over ``n`` can differ), and every other byte of the
leaf, the rows that are not live and the rows of other layers, BIT for bit
what went in. The planted faults are the two the issue names: the state
rounded to bfloat16 inside the body, and the select left out.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels import ssm_step as kernel

BODY = kernel._kernel
LAYERS, ROWS = 5, 4
LIVE = {"all": [1, 1, 1, 1], "none": [0, 0, 0, 0], "mixed": [1, 0, 0, 1]}
# (h, p, n), bytes of a tile, blocks of heads that makes: a state smaller than a
# tile (and than the chip's (8, 128)), taken whole, and one cut into two and
# into four by a tile made small, as granite-4.0-h-micro's 2 MiB is cut into
# two of 1 MiB
SHAPES = {"whole": ((8, 16, 16), kernel._TILE, 1), "two_blocks": ((16, 8, 128), 32 << 10, 2),
          "four_blocks": ((32, 8, 128), 32 << 10, 4)}


def operands(shape, seed=0, dtype=np.float32):
    h, p, n = shape
    rng = np.random.RandomState(seed)
    state = rng.randn(LAYERS * ROWS, h, p, n).astype(dtype)
    decay = np.exp(-np.abs(rng.randn(ROWS, h))).astype(np.float32)
    dtx = (0.3 * rng.randn(ROWS, h, p)).astype(np.float32)
    B, C = (rng.randn(ROWS, n).astype(np.float32) for _ in range(2))
    return state, decay, dtx, B, C


def recurrence(rows, decay, dtx, B, C):
    """The mixer's expressions on the layer's own rows."""
    S = decay[..., None, None] * rows + dtx[..., None] * B[:, None, None, :]
    return S, np.einsum("bhpn,bn->bhp", S, C)


def check(shape, tile, blocks, layer, live, monkeypatch):
    monkeypatch.setattr(kernel, "_TILE", tile)
    assert shape[0] // kernel.head_block(*shape) == blocks
    state, decay, dtx, B, C = operands(shape)
    live = np.asarray(live, bool)
    first = layer * ROWS
    got, y = jax.jit(lambda s, f: kernel.ssm_step(s, f, decay, dtx, B, C, live))(
        state, jnp.int32(first))
    got, y = np.asarray(got), np.asarray(y)
    mine = slice(first, first + ROWS)
    S, want_y = recurrence(state[mine], decay, dtx, B, C)
    np.testing.assert_allclose(got[mine][live], S[live], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-6, atol=1e-5)
    # a row that is not live: its state as it was, and y from that state
    np.testing.assert_array_equal(got[mine][~live], state[mine][~live])
    np.testing.assert_allclose(y[~live], np.einsum("bhpn,bn->bhp", state[mine], C)[~live],
                               rtol=1e-6, atol=1e-5)
    others = np.ones(len(state), bool)
    others[mine] = False
    np.testing.assert_array_equal(got[others], state[others])


@pytest.mark.parametrize("layer", [0, 2, LAYERS - 1], ids=["first", "middle", "last"])
@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_equals_the_recurrence_and_leaves_the_rest_alone(shape, live, layer,
                                                                    monkeypatch):
    check(*SHAPES[shape], layer, LIVE[live], monkeypatch)


def test_without_live_every_row_is_stepped():
    state, decay, dtx, B, C = operands((8, 16, 16))
    got, y = kernel.ssm_step(jnp.asarray(state), ROWS, decay, dtx, B, C)
    S, want_y = recurrence(state[ROWS: 2 * ROWS], decay, dtx, B, C)
    np.testing.assert_allclose(np.asarray(got)[ROWS: 2 * ROWS], S, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=1e-6, atol=1e-5)


def test_a_leaf_of_another_dtype_is_stepped_in_float32_and_rounded_once():
    state, decay, dtx, B, C = operands((8, 16, 16))
    leaf = jnp.asarray(state, jnp.bfloat16)
    live = np.asarray(LIVE["mixed"], bool)
    got, y = kernel.ssm_step(leaf, 0, decay, dtx, B, C, live)
    assert got.dtype == jnp.bfloat16 and y.dtype == jnp.float32
    rows = np.asarray(leaf[:ROWS], np.float32)
    S, want_y = recurrence(rows, decay, dtx, B, C)
    got = np.asarray(got[:ROWS], np.float32)
    np.testing.assert_array_equal(got[~live], rows[~live])
    np.testing.assert_allclose(got[live], np.asarray(jnp.asarray(S, jnp.bfloat16), np.float32)[live],
                               rtol=1e-2)      # an ulp of bfloat16 where the sums differ in the last bit
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live], rtol=1e-6, atol=1e-5)


class _Rounded:
    """The state's tile as a body would see it after a bfloat16 pass."""

    def __init__(self, ref):
        self.ref = ref

    def __getitem__(self, at):
        return self.ref[at].astype(jnp.bfloat16).astype(jnp.float32)


class _EveryRowLive:
    def __getitem__(self, at):
        return 1


def _bf16_state(first_ref, live_ref, state_ref, *refs):
    return BODY(first_ref, live_ref, _Rounded(state_ref), *refs)


def _no_select(first_ref, live_ref, state_ref, *refs):
    return BODY(first_ref, _EveryRowLive(), state_ref, *refs)


@pytest.mark.parametrize("fault", [_bf16_state, _no_select], ids=["bf16_state", "no_select"])
def test_a_planted_fault_fails(fault, monkeypatch):
    """The same check, with the body swapped for a wrong one: a state that
    takes a bfloat16 pass misses 1e-6 on the live rows and the bits of the
    others; without the select a row that is not live moves."""
    check(*SHAPES["two_blocks"], 2, LIVE["mixed"], monkeypatch)
    monkeypatch.setattr(kernel, "_kernel", fault)
    with pytest.raises(AssertionError):
        check(*SHAPES["two_blocks"], 2, LIVE["mixed"], monkeypatch)


def test_the_head_block_follows_the_shapes():
    assert kernel.head_block(64, 64, 128) == 32          # granite-4.0-h-micro: 1 MiB tiles
    assert kernel.head_block(128, 64, 128) == 32         # more heads, the same tile
    assert kernel.head_block(64, 64, 128, itemsize=2) == 64 and kernel.head_block(8, 16, 16) == 8
    assert kernel.head_block(24, 64, 256) == 8           # 8 is the smallest block that is not whole


@pytest.mark.parametrize("bad", [dict(decay=np.ones((ROWS, 7), np.float32)),
                                 dict(dtx=np.ones((ROWS, 8, 4), np.float32)),
                                 dict(B=np.ones((ROWS, 5), np.float32))],
                         ids=["decay", "dtx", "B"])
def test_shapes_that_do_not_fit_are_refused(bad):
    state, decay, dtx, B, C = operands((8, 16, 16))
    kw = dict(dict(decay=decay, dtx=dtx, B=B, C=C), **bad)
    with pytest.raises(ValueError, match="ssm_step"):
        kernel.ssm_step(jnp.asarray(state), 0, **kw)
