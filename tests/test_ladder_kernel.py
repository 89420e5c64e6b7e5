"""The device ladder kernel's move step (``repro.core.refine.device``).

Inside the temperature scan every per-row lookup and write is a dense,
lane-parallel masked select or compare-and-sum, never an indexed gather or
scatter.  Two contracts hold it to the gather / ``segment_sum`` form it
replaced:

* **parity** — on random rows and (p, q) pairs drawn from the boundary, the
  swap's count delta, its neighbour node values and the swapped rows equal
  the plain gather / ``segment_sum`` reference kept below, integer for
  integer, on every stencil shape the kernel meets (5- and 7-point, hops,
  weighted, and a periodic grid where a position is its own neighbour or
  the same neighbour twice);
* **pin** — the whole kernel, two temperatures on fixed inputs (rows dead
  and rows done among them), reproduces recorded digests of all ten
  outputs bit for bit: the same walks, keys and best-seen states.
"""
import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.core import CartGrid, Stencil
from repro.core.cost_delta import neighbor_table, stacked_count_arrays
from repro.core.refine.device import _temperature_kernel, _threefry_keys

#: dyadic edge weights, so float32 keys of integer counts are exact
W_STENCIL = Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)),
                    (2.0, 2.0, 0.5, 0.5), name="ring-dyadic")


def _rows(grid, n_nodes, n_rows, seed):
    """(n_rows, p) int32 balanced assignments, each its own permutation;
    the last row puts every position on node 0 (no boundary: done)."""
    rng = np.random.default_rng(seed)
    p = grid.size
    base = np.arange(p) % n_nodes
    node = np.stack([rng.permutation(base) for _ in range(n_rows)])
    node[-1] = 0
    return node.astype(np.int32)


def _kernel_inputs(grid, stencil, n_nodes, n_rows, seed):
    """The kernel's 15 arguments on a fixed problem: row 1 done, row 2
    dead (not live), rows at temperatures from hot to cold."""
    table = neighbor_table(grid, stencil)
    node = _rows(grid, n_nodes, n_rows, seed)
    co, cn = stacked_count_arrays(table, node, n_nodes)
    w = stencil.weight_array()
    jmax = (cn * w[None, None, :]).sum(axis=2).max(axis=1)
    jsum = (co * w[None, :]).sum(axis=1)
    done = np.zeros(n_rows, dtype=bool)
    done[1] = True
    live = np.ones(n_rows, dtype=bool)
    live[2] = False
    return [jnp.asarray(node), jnp.asarray(cn, jnp.int32),
            jnp.asarray(_threefry_keys(range(seed, seed + n_rows))),
            jnp.asarray(node), jnp.asarray(jmax, jnp.float32),
            jnp.asarray(jsum, jnp.float32), jnp.asarray(done),
            jnp.asarray(live),
            jnp.asarray(np.geomspace(4.0, 0.25, n_rows), jnp.float32),
            jnp.full(n_rows, 1e-2, jnp.float32),
            jnp.asarray(w, jnp.float32), jnp.asarray(table.out_valid),
            jnp.asarray(table.out_tgt, jnp.int32),
            jnp.asarray(table.in_valid),
            jnp.asarray(table.in_src, jnp.int32)]


def _kernel_digest(grid, stencil, n_nodes, n_rows, seed, moves):
    """sha256 over the dtype, shape and bytes of all ten outputs of two
    chained temperatures (the second at half the first's temperatures)."""
    args = _kernel_inputs(grid, stencil, n_nodes, n_rows, seed)
    kernel = _temperature_kernel(moves)
    h = hashlib.sha256()
    for _ in range(2):
        out = kernel(*args)
        for x in out:
            x = np.asarray(x)
            h.update(f"{x.dtype}{x.shape}".encode())
            h.update(np.ascontiguousarray(x).tobytes())
        args[:7] = out[:7]
        args[8] = args[8] * 0.5
    return h.hexdigest()


# ---------------------------------------------------------------------------
# parity: the dense move step against the gather / segment_sum reference


def _reference_move(node, pi, qi, a, b, n_nodes, out_valid, out_tgt,
                    in_valid, in_src):
    """The move step's lookups, count delta and row swap as indexed
    gathers, ``segment_sum`` scatter-adds and ``.at[].set`` scatters, one
    ladder at a time (the plain form the kernel's dense one must equal)."""
    k = out_tgt.shape[0]
    N = n_nodes

    def ladder_delta(node_r, p_r, q_r, a_r, b_r):
        src = jnp.concatenate([
            jnp.full((k,), p_r, dtype=node_r.dtype),
            jnp.full((k,), q_r, dtype=node_r.dtype),
            in_src[:, p_r], in_src[:, q_r]])
        dst = jnp.concatenate([
            out_tgt[:, p_r], out_tgt[:, q_r],
            jnp.full((k,), p_r, dtype=node_r.dtype),
            jnp.full((k,), q_r, dtype=node_r.dtype)])
        valid = jnp.concatenate([
            out_valid[:, p_r], out_valid[:, q_r],
            in_valid[:, p_r] & (in_src[:, p_r] != p_r)
            & (in_src[:, p_r] != q_r),
            in_valid[:, q_r] & (in_src[:, q_r] != p_r)
            & (in_src[:, q_r] != q_r)])
        off = jnp.tile(jnp.arange(k, dtype=jnp.int32), 4)

        def remap(x):
            return jnp.where(x == p_r, b_r,
                             jnp.where(x == q_r, a_r, node_r[x]))

        s_old, d_old = node_r[src], node_r[dst]
        s_new, d_new = remap(src), remap(dst)
        old_c = valid & (s_old != d_old)
        new_c = valid & (s_new != d_new)
        dec = jax.ops.segment_sum(old_c.astype(jnp.int32),
                                  s_old * k + off, num_segments=N * k)
        inc = jax.ops.segment_sum(new_c.astype(jnp.int32),
                                  s_new * k + off, num_segments=N * k)
        other = jnp.stack([out_tgt[:, p_r], out_tgt[:, q_r],
                           in_src[:, p_r], in_src[:, q_r]])
        return node_r[other], (inc - dec).reshape(N, k)

    return jax.vmap(ladder_delta)(node, pi, qi, a, b)


def _boundary_pairs(table, node, rng):
    """Per row, p drawn from the boundary and q from the boundary
    positions on another node (the kernel's proposal support)."""
    cross = np.zeros(node.shape, dtype=bool)
    for j in range(table.out_valid.shape[0]):
        c = table.out_valid[j][None] & (node != node[:, table.out_tgt[j]])
        cross |= c
        np.logical_or.at(cross, (np.nonzero(c)[0],
                                 table.out_tgt[j][np.nonzero(c)[1]]), True)
    pi, qi = [], []
    for r in range(node.shape[0]):
        p = rng.choice(np.flatnonzero(cross[r]))
        pi.append(p)
        qi.append(rng.choice(np.flatnonzero(cross[r]
                                            & (node[r] != node[r, p]))))
    return np.asarray(pi, np.int32), np.asarray(qi, np.int32)


@pytest.mark.parametrize("grid, stencil, n_nodes", [
    (CartGrid((8, 7)), Stencil.nearest_neighbor(2), 4),
    (CartGrid((5, 4, 3)), Stencil.nearest_neighbor(3), 6),
    (CartGrid((9, 6)), Stencil.nn_with_hops(2, hops=(2,)), 5),
    (CartGrid((7, 6)), Stencil(((1, 1), (-1, 0), (0, 2), (2, -1)),
                               (3.0, 1.0, 0.5, 2.0), name="skew-weighted"),
     4),
    # a position is its own neighbour along the size-1 axis and has the
    # same neighbour twice along the size-2 one
    (CartGrid((1, 2, 5), periodic=(True, True, True)),
     Stencil.nearest_neighbor(3), 3),
], ids=["5pt", "7pt", "hops2", "weighted", "periodic"])
def test_dense_move_step_equals_gather_reference(grid, stencil, n_nodes):
    from repro.core.refine.device import (_row_lookup, _swap_delta,
                                          _swap_rows)
    table = neighbor_table(grid, stencil)
    rng = np.random.default_rng(grid.size * 31 + stencil.k)
    node = _rows(grid, n_nodes, 33, seed=int(rng.integers(1 << 30)))[:-1]
    pi, qi = _boundary_pairs(table, node, rng)
    tabs = (jnp.asarray(table.out_valid),
            jnp.asarray(table.out_tgt, jnp.int32),
            jnp.asarray(table.in_valid), jnp.asarray(table.in_src, jnp.int32))
    jnode, jpi, jqi = jnp.asarray(node), jnp.asarray(pi), jnp.asarray(qi)
    rows = np.arange(node.shape[0])
    a = np.asarray(_row_lookup(jnode, jpi[:, None]))[:, 0]
    b = np.asarray(_row_lookup(jnode, jqi[:, None]))[:, 0]
    np.testing.assert_array_equal(a, node[rows, pi])
    np.testing.assert_array_equal(b, node[rows, qi])

    nbr, d_cn = _swap_delta(jnode, jpi, jqi, jnp.asarray(a), jnp.asarray(b),
                            n_nodes, *tabs)
    ref_nbr, ref_d = _reference_move(jnode, jpi, jqi, jnp.asarray(a),
                                     jnp.asarray(b), n_nodes, *tabs)
    assert d_cn.dtype == ref_d.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(d_cn), np.asarray(ref_d))
    # neighbour values: equal wherever the edge exists (0 elsewhere)
    valid = np.stack([table.out_valid[:, pi].T, table.out_valid[:, qi].T,
                      table.in_valid[:, pi].T, table.in_valid[:, qi].T],
                     axis=1)
    np.testing.assert_array_equal(np.where(valid, np.asarray(ref_nbr), 0),
                                  np.asarray(nbr))

    accept = rng.random(node.shape[0]) < 0.5
    swapped = np.asarray(_swap_rows(jnode, jpi, jqi, jnp.asarray(a),
                                    jnp.asarray(b), jnp.asarray(accept)))
    ref_sw = jnode.at[rows, jpi].set(jnp.asarray(b)).at[rows, jqi].set(
        jnp.asarray(a))
    np.testing.assert_array_equal(
        swapped, np.where(accept[:, None], np.asarray(ref_sw), node))
    # and the delta is the recount's: counts(old) + d_cn == counts(new)
    _, cn_old = stacked_count_arrays(table, node, n_nodes)
    _, cn_new = stacked_count_arrays(table, np.asarray(ref_sw), n_nodes)
    np.testing.assert_array_equal(cn_old + np.asarray(d_cn), cn_new)


# ---------------------------------------------------------------------------
# pin: the whole kernel, bit for bit

#: sha256 of both temperatures' ten outputs, recorded from the kernel's
#: gather / scatter form (same inputs, CPU backend): the dense form must
#: walk the same walks
PINNED = {
    "2d": "c658ad78486d66bbdca77a07f7f028de9a5887776d2c79e1687ca0694a20ace2",
    "3d": "7a65aa2fdac2001e758dba910666c1fed516035fd17d4e79ae7dd3f3261d6a5f",
    "periodic":
        "6d55a73d9bd8f57b1adcb39242f28b8d164b70fb7baebe5a6a4ddec81f5c1ef5",
}


@pytest.mark.parametrize("case, grid, stencil, n_nodes, n_rows, seed", [
    ("2d", CartGrid((6, 7)), W_STENCIL, 5, 8, 11),
    ("3d", CartGrid((4, 4, 3)), Stencil.nearest_neighbor(3), 4, 8, 23),
    ("periodic", CartGrid((1, 2, 6), periodic=(True, True, True)),
     Stencil.nearest_neighbor(3), 3, 6, 37),
], ids=["2d", "3d", "periodic"])
def test_kernel_reproduces_pinned_walks(case, grid, stencil, n_nodes,
                                        n_rows, seed):
    assert _kernel_digest(grid, stencil, n_nodes, n_rows, seed,
                          moves=40) == PINNED[case]
