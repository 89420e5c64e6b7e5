"""Distributed 2-d Jacobi sweep on a (mapped) device mesh: the paper's
application domain.

The global array is sharded over both mesh axes.  Each sweep exchanges
one-deep halos with ``jax.lax.ppermute`` along each axis (the
``MPI_Neighbor_alltoall`` analog), then applies the 5-point update to the
local block in jnp.  The boundary is zero (non-periodic), so the result
must equal :func:`jacobi_oracle`'s single-array iteration whatever device
order the mesh holds: the mapping changes which chips talk, never the
answer.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["JACOBI_WEIGHTS", "jacobi_taps", "halo_pad", "jacobi_step_local",
           "jacobi_sweeps", "distributed_jacobi", "jacobi_oracle"]

#: centre, north, south, west, east
JACOBI_WEIGHTS = (0.4, 0.15, 0.15, 0.15, 0.15)


def jacobi_taps(stencil):
    """The Pallas stencil kernel's ``(offsets, weights, halo)`` for the
    Jacobi update over a communication
    :class:`~repro.core.stencil.Stencil`: the centre keeps
    ``JACOBI_WEIGHTS[0]`` and the stencil's neighbours share the rest
    equally (on the 5-point stencil, :data:`JACOBI_WEIGHTS` itself).
    ``halo`` is the stencil's reach, the halo width the exchange must
    deliver."""
    centre = JACOBI_WEIGHTS[0]
    offsets = ((0,) * stencil.ndim,) + tuple(stencil.offsets)
    weights = (centre,) + ((1.0 - centre) / stencil.k,) * stencil.k
    halo = max(abs(x) for off in stencil.offsets for x in off)
    return offsets, weights, halo


def halo_pad(u, axis_name: str, size: int, axis: int):
    """Exchange one-deep halos along a mesh axis of ``size`` shards
    (non-periodic: the outer shards receive zeros)."""
    fwd = [(i, i + 1) for i in range(size - 1)]
    bwd = [(i, i - 1) for i in range(1, size)]
    last = jax.lax.slice_in_dim(u, u.shape[axis] - 1, u.shape[axis], axis=axis)
    first = jax.lax.slice_in_dim(u, 0, 1, axis=axis)
    from_left = jax.lax.ppermute(last, axis_name, fwd)
    from_right = jax.lax.ppermute(first, axis_name, bwd)
    return jnp.concatenate([from_left, u, from_right], axis=axis)


def jacobi_step_local(u_halo, weights: Sequence[float] = JACOBI_WEIGHTS):
    """The 5-point update of one haloed local block."""
    c, n_, s_, w_, e_ = weights
    return (c * u_halo[1:-1, 1:-1] + n_ * u_halo[:-2, 1:-1]
            + s_ * u_halo[2:, 1:-1] + w_ * u_halo[1:-1, :-2]
            + e_ * u_halo[1:-1, 2:])


def jacobi_sweeps(mesh: Mesh, iters: int,
                  weights: Sequence[float] = JACOBI_WEIGHTS):
    """The jitted ``iters``-sweep program over the two axes of ``mesh``:
    it takes and returns the global array sharded ``P(axis0, axis1)``."""
    ax0, ax1 = mesh.axis_names
    n0, n1 = mesh.devices.shape

    def step(u):
        u = halo_pad(u, ax0, n0, 0)
        u = halo_pad(u, ax1, n1, 1)
        return jacobi_step_local(u, weights)

    dist_step = jax.shard_map(step, mesh=mesh, in_specs=P(ax0, ax1),
                              out_specs=P(ax0, ax1))

    @jax.jit
    def run(u):
        for _ in range(iters):
            u = dist_step(u)
        return u

    return run


def distributed_jacobi(mesh: Mesh, u0, iters: int,
                       weights: Sequence[float] = JACOBI_WEIGHTS) \
        -> np.ndarray:
    """``iters`` Jacobi sweeps of ``u0`` sharded over the two axes of
    ``mesh``; returns the gathered result."""
    u = jax.device_put(u0, NamedSharding(mesh, P(*mesh.axis_names)))
    return np.asarray(jacobi_sweeps(mesh, iters, weights)(u))


def jacobi_oracle(u0, iters: int,
                  weights: Sequence[float] = JACOBI_WEIGHTS) -> np.ndarray:
    """The same sweeps on one host array (numpy, the input's dtype)."""
    ref = np.asarray(u0)
    c, n_, s_, w_, e_ = weights
    for _ in range(iters):
        pad = np.pad(ref, 1)
        ref = (c * pad[1:-1, 1:-1] + n_ * pad[:-2, 1:-1]
               + s_ * pad[2:, 1:-1] + w_ * pad[1:-1, :-2]
               + e_ * pad[1:-1, 2:])
    return ref
