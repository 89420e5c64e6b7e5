"""The paper's application domain, end to end: a distributed 2-d Jacobi
stencil solve with halo exchange, on a *mapped* device mesh.

This script runs with 8 XLA host devices (set below, before jax imports —
this is an example launcher, like dryrun.py) arranged as 2 "nodes" x 4
"cores".  It:

  1. computes the process-to-node mapping with a paper algorithm and builds
     the jax Mesh from the permuted device array (MPI_Cart_create reorder);
  2. runs Jacobi iterations under shard_map, exchanging halos with
     jax.lax.ppermute — the MPI_Neighbor_alltoall analog — and applying
     the local 5-point update in jnp
     (:mod:`repro.kernels.stencil.jacobi`);
  3. checks the distributed result against a single-array oracle and prints
     the J_sum/J_max table for the chosen vs blocked layout.

Run:  PYTHONPATH=src python examples/stencil_jacobi.py --mapper stencil_strips
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import (Stencil, device_layout, get_mapper, layout_cost,
                        mapped_device_array)
from repro.kernels.stencil.jacobi import distributed_jacobi, jacobi_oracle

MESH_SHAPE = (4, 2)      # logical process grid
CHIPS_PER_NODE = 4       # 8 devices = 2 "nodes" of 4


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mapper", default="stencil_strips")
    ap.add_argument("--size", type=int, default=256)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    stencil = Stencil.nearest_neighbor(2)

    # 1. mapped mesh (the paper's reorder step)
    devs = mapped_device_array(jax.devices(), get_mapper(args.mapper),
                               MESH_SHAPE, stencil, CHIPS_PER_NODE)
    mesh = Mesh(devs, ("x", "y"))

    # mapping quality vs blocked
    sizes = [CHIPS_PER_NODE] * (8 // CHIPS_PER_NODE)
    print(f"{'layout':16s} {'J_sum':>8s} {'J_max':>8s}")
    for algo in ("blocked", args.mapper, "random"):
        L = device_layout(get_mapper(algo), MESH_SHAPE, stencil, sizes)
        c = layout_cost(L, stencil, sizes)
        print(f"{algo:16s} {c.j_sum:8.0f} {c.j_max:8.0f}")

    # 2-3. distributed Jacobi under shard_map vs the single-array oracle
    n = args.size
    u0 = jnp.zeros((n, n), jnp.float32).at[n // 2, n // 2].set(1000.0)
    out = distributed_jacobi(mesh, u0, args.iters)
    err = np.abs(out - jacobi_oracle(u0, args.iters)).max()
    print(f"\ndistributed Jacobi x{args.iters} on {MESH_SHAPE} mesh "
          f"({args.mapper} layout): max|err| vs oracle = {err:.2e}")
    assert err < 1e-4, "distributed result diverged from oracle"
    print("OK")


if __name__ == "__main__":
    main()
