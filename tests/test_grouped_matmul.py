"""The grouped matmul kernel alone (``kernels/grouped_matmul.py``), interpreted
on the CPU at small ``k`` / ``n``: every row of a group against a plain
``lhs[r] @ rhs[layer, group(r)]``, over the tile plans :func:`row_tile` gives
(one tile up to 256 rows; 512- or 1 024-row tiles cut into sub-tiles above)
and the group layouts that meet a tile's and a sub-tile's edges."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kernels import grouped_matmul as gm

K, N, LAYERS = 128, 256, 2


def _ends_inside_a_sub_tile(m, tm, sub):
    """Groups whose last rows sit in the middle of a sub-tile, the next
    group's first rows in the same one."""
    a = min(m // 4, sub + sub // 2 + 3)
    return [a, max(1, m // 3), 0, m // 5]


def _smaller_than_a_sub_tile(m, tm, sub):
    """Several groups inside one sub-tile, then one across the next edge."""
    small = [1, 2, 3, 0, 5, 1]
    return [s for s in small if sum(small) <= m // 2] + [min(m // 3, sub + 1)]


def _spans_three_tiles(m, tm, sub):
    """A group from the middle of a tile to the middle of the tile after the
    next (as far as ``m`` has tiles), one before and one after it."""
    before = min(m // 8, tm // 2 + 5)
    return [before, min(m - before - m // 8, 2 * tm + 11), m // 16]


def _empty_first_last_between(m, tm, sub):
    return [0, 0, m // 3, 0, 0, m // 4 + 1, 0]


def _all_rows_dead(m, tm, sub):
    return [0, 0, 0, 0]


def _rows_beyond_the_groups(m, tm, sub):
    """The groups end early: the tail belongs to nobody (whole tiles of it
    where ``m`` has several) and is in no visit."""
    return [m // 8, 0, m // 8 + 1]


LAYOUTS = [_ends_inside_a_sub_tile, _smaller_than_a_sub_tile, _spans_three_tiles,
           _empty_first_last_between, _all_rows_dead, _rows_beyond_the_groups]


def _sub_tiles_touched(sizes, sub):
    """A numpy count, group by group: the sub-tiles that hold one of its rows."""
    ends = np.cumsum(sizes)
    return sum((e - 1) // sub - (e - n) // sub + 1 for e, n in zip(ends, sizes) if n)


@pytest.mark.parametrize("stacks", [1, 2], ids=["one_stack", "two_stacks"])
@pytest.mark.parametrize("layout", LAYOUTS, ids=[f.__name__[1:] for f in LAYOUTS])
@pytest.mark.parametrize("m", [16, 64, 512, 1024, 4096])
def test_every_row_of_a_group_is_its_own_product(m, layout, stacks):
    """``out[r] = finish(lhs[r] @ rhs[i][layer, group(r)] ...)`` for every row
    of a group: bf16 operands, float32 sums, so the plain product in float32
    agrees to the order of additions. Rows of no group are unspecified and
    not looked at. The count behind ``moe_insert_rows_multiplied`` is the
    sub-tiles the groups touch, counted here in numpy."""
    about = gm.row_tile(m, 8)[0]      # the tile, near enough to aim at its edges
    sizes = np.asarray(layout(m, about, gm.sub_tile(about)), np.int32)
    E = len(sizes)
    tm, rows = gm.row_tile(m, E)
    sub = gm.sub_tile(tm)
    assert sizes.sum() <= m and rows % tm == 0 and tm % sub == 0 and sub % 16 == 0
    rs = np.random.RandomState(m + E)
    lhs = jnp.asarray(rs.randn(rows, K), jnp.bfloat16)
    rhs = tuple(jnp.asarray(rs.randn(LAYERS, E, K, N) / 8, jnp.bfloat16)
                for _ in range(stacks))
    visits = gm.group_visits(jnp.asarray(sizes), rows, tm)
    finish = (lambda a, b: a * b) if stacks == 2 else gm._as_is
    out = gm.grouped_matmul(lhs, rhs, jnp.int32(1), visits, tm, finish,
                            out_dtype=jnp.float32)
    assert out.shape == (rows, N)
    group = np.repeat(np.arange(E), sizes)
    real = len(group)
    products = [np.einsum("rk,rkn->rn", np.asarray(lhs, np.float32)[:real],
                          np.asarray(w, np.float32)[1][group]) for w in rhs]
    want = products[0] * products[1] if stacks == 2 else products[0]
    np.testing.assert_allclose(np.asarray(out)[:real], want, rtol=1e-4, atol=1e-4)
    multiplied = int(gm.rows_multiplied(jnp.asarray(sizes), tm))
    assert multiplied == _sub_tiles_touched(sizes, sub) * sub
    assert real <= multiplied <= int(visits.count) * tm


@pytest.mark.parametrize("E", [4, 8, 20, 64])
def test_one_tile_is_one_sub_tile_and_the_kernel_has_no_loop(E, monkeypatch):
    """Up to 256 rows (every decode step: 16 rows for Mixtral, 64 for OLMoE,
    48 for DeepSeek-V2) the plan is one tile of one sub-tile, the kernel is
    asked for with ``sub == tm`` and its body holds no loop over sub-tiles;
    above, the tile is 512 rows or more and the body loops."""
    for m in range(1, 257):
        tm, rows = gm.row_tile(m, E)
        assert tm == rows == -(-m // 16) * 16 and gm.sub_tile(tm) == tm
    for m in (257, 512, 1000, 4096, 8192, 32768):
        tm, rows = gm.row_tile(m, E)
        assert tm >= min(512, rows) and rows % tm == 0 and gm.sub_tile(tm) < tm
        assert tm <= max(512, m // E)     # no larger than a group's mean rows
    asked = []
    kernel_for = gm._kernel_for
    monkeypatch.setattr(gm, "_kernel_for",
                        lambda *a: asked.append(a) or kernel_for(*a))

    def body(m):
        tm, rows = gm.row_tile(m, E)
        sizes = jnp.full((E,), m // E, jnp.int32)
        return str(jax.make_jaxpr(lambda lhs, rhs: gm.grouped_matmul(
            lhs, (rhs,), jnp.int32(0), gm.group_visits(sizes, rows, tm), tm))(
                jnp.zeros((rows, K), jnp.bfloat16),
                jnp.zeros((1, E, K, N), jnp.bfloat16)))

    for m in (16, 48, 64, 256):
        assert "while" not in body(m)
        tm, sub, tiles_k, _ = asked[-1]
        assert tm == sub == gm.row_tile(m, E)[0] and tiles_k == 1
    assert "while" in body(1024)
    assert asked[-1][1] == gm.sub_tile(asked[-1][0]) < asked[-1][0]
