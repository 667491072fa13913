"""The partition pass's row movement against the scatter it replaced.

``route_window`` (what ``pbody`` of the fused tree program calls: two
``compact`` networks and two masked window writes) must leave the destination buffers exactly as the old
``.at[pos].set(..., mode="drop")`` pair did: the same ids and the same
payload at the same rows. The scatter lives on here as the oracle, not in
the program. The oracle holds the payload ``[N + W, SW]`` as that commit
did; the pass holds it word-major (``[SW, N + W]``, lanes and positions on
the last axis: PR 36), so its side is the oracle's transpose.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lambdagap_tpu.ops.partition import compact, route_window


def _trip_oracle(pbuf, sbuf, rows, dw, gl, live, lcur, rcur, N):
    """pbody's movement as the parent commit had it."""
    W = rows.shape[0]
    lane = jnp.arange(W, dtype=jnp.int32)
    valid = lane < live
    gl = gl & valid
    cums_gl = jnp.cumsum(gl.astype(jnp.int32))
    nl = cums_gl[W - 1]
    prefix_valid = jnp.minimum(lane + 1, live)
    lpos = lcur + cums_gl - 1
    rpos = rcur - (prefix_valid - cums_gl)
    pos = jnp.where(gl, lpos, jnp.where(valid, rpos, N))
    return (pbuf.at[pos].set(rows, mode="drop"),
            sbuf.at[pos].set(dw, mode="drop"), nl)


def _trip_compact(pbuf, sbuf, rows, dw, gl, live, lcur, rcur, N):
    W = rows.shape[0]
    valid = jnp.arange(W, dtype=jnp.int32) < live
    gl = gl & valid
    gr = ~gl & valid
    (pbuf, sbuf), nl, nr = route_window((pbuf, sbuf.T), (rows, dw.T), gl,
                                        gr, lcur, rcur)
    return pbuf, sbuf.T, nl


ORACLE = jax.jit(_trip_oracle, static_argnames=("N",))
COMPACT = jax.jit(_trip_compact, static_argnames=("N",))


def _partition_leaf(trip, perm, srows, begin, count, gl_of_row, N, W):
    """The while loop of the pass, trip by trip, into fresh buffers whose
    every row is marked so that a write that should not happen shows."""
    pbuf = jnp.full((N + W,), -7, jnp.int32)
    sbuf = jnp.full((N + W, srows.shape[1]), 0xABCD, jnp.uint32)
    lcur, rcur = begin, begin + count
    for c in range(-(-count // W) if count else 1):
        start = begin + c * W
        live = int(np.clip(count - c * W, 0, W))
        rows = jax.lax.dynamic_slice(perm, (start,), (W,))
        dw = jax.lax.dynamic_slice(srows, (start, 0), (W, srows.shape[1]))
        pbuf, sbuf, nl = trip(
            pbuf, sbuf, rows, dw, gl_of_row[rows], jnp.int32(live),
            jnp.int32(lcur), jnp.int32(rcur), N=N)
        lcur, rcur = lcur + int(nl), rcur - (live - int(nl))
    assert lcur == rcur
    return np.asarray(pbuf), np.asarray(sbuf), lcur - begin


def _pattern(name, n, rng):
    return {"left": np.ones(n, bool), "right": np.zeros(n, bool),
            "alternating": np.arange(n) % 2 == 0,
            "random": rng.rand(n) < rng.rand()}[name]


def _case(W, SW, N, begin, count, pattern):
    rng = np.random.RandomState(W + 31 * SW + begin + 7 * count)
    perm = jnp.asarray(np.concatenate(
        [rng.permutation(N), np.full(W, N)]).astype(np.int32))
    srows = jnp.asarray(rng.randint(0, 2**31, (N + W, SW)).astype(np.uint32))
    # indexed by row id; the pad's id N reads the last entry
    gl_of_row = jnp.asarray(np.append(_pattern(pattern, N, rng), True))
    want = _partition_leaf(ORACLE, perm, srows, begin, count, gl_of_row, N, W)
    got = _partition_leaf(COMPACT, perm, srows, begin, count, gl_of_row, N, W)
    assert got[2] == want[2]
    # rows [0, N) are the contract; row N is where the scatter dumped the
    # dead lanes, and the window writes leave the whole pad as it was
    assert np.array_equal(got[0][:N], want[0][:N])
    assert np.array_equal(got[1][:N], want[1][:N])
    assert (got[0][N:] == -7).all() and (got[1][N:] == 0xABCD).all()
    untouched = np.ones(N, bool)
    untouched[begin:begin + count] = False
    assert (got[0][:N][untouched] == -7).all()


LIVES = {"0": lambda W: 0, "1": lambda W: 1, "W-1": lambda W: W - 1,
         "W": lambda W: W}


@pytest.mark.parametrize("where", ["first_leaf", "buffer_end"])
@pytest.mark.parametrize("pattern", ["left", "right", "alternating",
                                     "random"])
@pytest.mark.parametrize("live", list(LIVES))
@pytest.mark.parametrize("SW", [1, 9, 35])
@pytest.mark.parametrize("W", [1024, 4096])
def test_one_window_equals_scatter(W, SW, live, pattern, where):
    """One trip: ``begin`` = 0 with ``count`` < W (the first leaf of every
    tree), and the last window of the buffer (the tail pad absorbs it)."""
    count = LIVES[live](W)
    N = W + W // 2 + 3
    begin = 0 if where == "first_leaf" else N - count
    _case(W, SW, N, begin, count, pattern)


@pytest.mark.parametrize("pattern", ["alternating", "random"])
@pytest.mark.parametrize("SW", [1, 9, 35])
@pytest.mark.parametrize("W", [1024, 4096])
def test_leaf_of_several_windows_equals_scatter(W, SW, pattern):
    """A leaf of three and a bit windows in the middle of the buffer: the
    rights of a later trip land below those of an earlier one, and a later
    window write must keep what an earlier one put outside its mask."""
    N = 5 * W + 11
    _case(W, SW, N, begin=W // 2 + 5, count=3 * W + W // 3, pattern=pattern)


@pytest.mark.parametrize("back", [False, True], ids=["front", "back"])
def test_compact_is_stable_and_exact(back):
    rng = np.random.RandomState(3)
    for W in (8, 64, 1024):
        keep = rng.rand(W) < 0.4
        ids = rng.randint(-2**31, 2**31 - 1, W).astype(np.int32)
        vals = rng.randn(3, W).astype(np.float32)
        a, b = compact(jnp.asarray(keep), jnp.asarray(ids),
                       jnp.asarray(vals), back=back)
        n = int(keep.sum())
        where = slice(W - n, W) if back else slice(0, n)
        assert np.array_equal(np.asarray(a)[where], ids[keep])
        assert np.array_equal(np.asarray(b)[:, where], vals[:, keep])
