"""The packed-key switch boundary (`bfs.PACKED_KEY_MAX_N`): at the
largest int32-safe n the doubling engine runs its packed single-scatter
relaxation; one node more and it falls back to the unpacked two-scatter
pass. Both sides of the switch must be bit-identical to the numpy
oracle's level-synchronous BFS — the graphs are sparse chains anchored
at the TOP of the id range so the packed keys actually reach their
maxima."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import _host as H
from repro.core.bfs import (
    EULER_PACK_MAX_N,
    PACKED_KEY_MAX_N,
    bfs_doubling,
    packed_key_bound,
)

INT32_MAX = np.int32(np.iinfo(np.int32).max)


def _top_chain_graph(n, length=9):
    """A chain over the `length` highest node ids (maximizing dist·
    (n+1)+id keys) plus one far spur; everything else unreachable."""
    hi = np.arange(n - length, n, dtype=np.int32)
    u = hi[:-1]
    v = hi[1:]
    # a spur from the chain's far end to node 0: max-id → min-id edge
    u = np.append(u, hi[0])
    v = np.append(v, np.int32(0))
    return jnp.asarray(u), jnp.asarray(v)


def test_constants_bracket_int32():
    assert packed_key_bound(PACKED_KEY_MAX_N) <= np.iinfo(np.int32).max
    assert packed_key_bound(PACKED_KEY_MAX_N + 1) > np.iinfo(np.int32).max
    assert PACKED_KEY_MAX_N == 46339
    assert EULER_PACK_MAX_N == 0xFFFF


@pytest.mark.parametrize("n", [PACKED_KEY_MAX_N, PACKED_KEY_MAX_N + 1])
def test_engines_bit_identical_across_switch(n):
    u, v = _top_chain_graph(n)
    root = jnp.int32(n - 1)
    dd, pd = bfs_doubling(u, v, n, root)
    dl, pl = H.bfs_np(np.asarray(u), np.asarray(v), n, n - 1)
    dd, pd = np.asarray(dd), np.asarray(pd)
    assert np.array_equal(dd, dl)
    assert np.array_equal(pd, pl)
    # sanity on the expected structure, not just mutual agreement
    assert dd[n - 1] == 0 and pd[n - 1] == -1
    assert dd[n - 9] == 8 and dd[0] == 9
    unreachable = np.ones(n, bool)
    unreachable[n - 9:] = False
    unreachable[0] = False
    assert np.all(dd[unreachable] == INT32_MAX)
    assert np.all(pd[unreachable] == -1)


@pytest.mark.parametrize("n", [PACKED_KEY_MAX_N, PACKED_KEY_MAX_N + 1])
def test_edge_mask_respected_across_switch(n):
    u, v = _top_chain_graph(n)
    # mask off the spur: node 0 must become unreachable
    mask = np.arange(len(u)) != len(u) - 1
    root = jnp.int32(n - 1)
    dd, pd = bfs_doubling(u, v, n, root, edge_mask=jnp.asarray(mask))
    dl, pl = H.bfs_np(np.asarray(u), np.asarray(v), n, n - 1, mask)
    assert np.array_equal(np.asarray(dd), np.asarray(dl))
    assert np.array_equal(np.asarray(pd), np.asarray(pl))
    assert np.asarray(dd)[0] == INT32_MAX
