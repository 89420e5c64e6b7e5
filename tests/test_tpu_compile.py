"""Compile the device path's kernels for a described TPU v5e, at the sizes
the chip runs them, with the TPU compiler that ships with jaxlib.

No chip is needed: ``jax.experimental.topologies`` describes a v5e, and
``jit(...).lower(shapes).compile()`` raises whatever Mosaic or XLA would
refuse on the chip (misaligned slices, VMEM overflow, HBM overflow).
Interpret-mode tests cannot see those faults.  A passing compile is not a
chip run: results and times come only from ``chip_smoke.py`` on a chip.

The topology is described inside a module fixture (never at import), and
the persistent compile cache is off around these compiles: an entry
compiled for a described chip cannot be read back without one.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import CartGrid, Stencil, cart_create
from repro.core.cost_delta import _jit_stacked_counts, neighbor_table
from repro.core.remap import apply_layout
from repro.core.refine.device import _temperature_kernel
from repro.core.refine.device_swap import CHUNK, _scores_kernel
from repro.kernels.stencil.ops import stencil_apply
from repro.kernels.stencil.jacobi import jacobi_sweeps, jacobi_taps
from repro.kernels.stencil.stencil import stencil3d_pallas

#: the per-chip Jacobi shard ``chip_smoke.py`` runs (plus its halo)
SHARD = 2048
#: the fleet-size device solve: a (64, 64) mesh over 256 pods of 16 chips,
#: K=1024 ladders plus as many restart slots, 200 moves per temperature
LADDER_DIMS, LADDER_PODS, LADDER_ROWS, LADDER_MOVES = (64, 64), 256, 2048, 200
#: v5e HBM per chip
HBM_BYTES = 16 * 2**30
#: the benchmark cells' device solve: 992 processes on 31 nodes, K=256
#: ladders plus as many restart slots
CELL_ROWS, CELL_P, CELL_NODES = 512, 992, 31


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the compile cache off around its use."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


@pytest.mark.parametrize("stencil", [
    Stencil.nearest_neighbor(2),                # the 5-point Jacobi
    Stencil.nn_with_hops(2, hops=(2,)),         # halo 2
], ids=["5pt", "halo2"])
def test_stencil_kernel_compiles_for_v5e(one_chip, stencil):
    offsets, weights, halo = jacobi_taps(stencil)
    u = _shape((SHARD + 2 * halo,) * 2, jnp.float32, one_chip)
    compiled = stencil_apply.lower(u, offsets, weights, halo,
                                   interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stencil3d_kernel_compiles_for_v5e(one_chip):
    offsets, weights, halo = jacobi_taps(Stencil.nearest_neighbor(3))
    u = _shape((64 + 2 * halo,) * 3, jnp.float32, one_chip)
    compiled = jax.jit(lambda x: stencil3d_pallas(x, offsets, weights,
                                                  halo)).lower(u).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _temperature_args(one_chip, R, p, N, k):
    i32, f32 = jnp.int32, jnp.float32
    return [_shape((R, p), i32, one_chip),          # node
            _shape((R, N, k), i32, one_chip),       # count state
            _shape((R, 2), jnp.uint32, one_chip),   # rng keys
            _shape((R, p), i32, one_chip),          # best node
            _shape((R,), f32, one_chip),            # best J_max
            _shape((R,), f32, one_chip),            # best J_sum
            _shape((R,), jnp.bool_, one_chip),      # done
            _shape((R,), jnp.bool_, one_chip),      # live
            _shape((R,), f32, one_chip),            # temperatures
            _shape((R,), f32, one_chip),            # eps
            _shape((k,), f32, one_chip),            # offset weights
            _shape((k, p), jnp.bool_, one_chip),    # out_valid
            _shape((k, p), i32, one_chip),          # out_tgt
            _shape((k, p), jnp.bool_, one_chip),    # in_valid
            _shape((k, p), i32, one_chip)]          # in_src


def test_temperature_kernel_compiles_for_v5e(one_chip):
    """The device refiner's one-temperature scan at fleet size fits HBM."""
    args = _temperature_args(one_chip, LADDER_ROWS, int(np.prod(LADDER_DIMS)),
                             LADDER_PODS, Stencil.nearest_neighbor(2).k)
    compiled = _temperature_kernel(LADDER_MOVES).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert 0 < used < HBM_BYTES


def _while_body_ops(hlo: str, ops=("gather", "scatter")):
    """``(op, operand element count)`` of every ``ops`` instruction in the
    computations a compiled module's ``while`` bodies reach."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = [line]
        elif name is not None:
            comps[name].append(line)
            if line == "}":
                name = None
    todo = re.findall(r"\bwhile\(.*?body=%([\w.\-]+)", hlo)
    assert todo, "no while loop in the compiled module"
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        todo += [r for r in re.findall(r"%([\w.\-]+)", "\n".join(comps[c]))
                 if r in comps]
    found = []
    for c in seen:
        text = "\n".join(comps[c])
        size = {n: math.prod(int(d) for d in dims.split(",") if d)
                for n, dims in re.findall(
                    r"%?([\w.\-]+):? (?:= )?\w+\[([\d,]*)\]", text)}
        for op, operand in re.findall(r" (%s)\(%%([\w.\-]+)" % "|".join(ops),
                                      text):
            found.append((op, size.get(operand)))
    return found


@pytest.mark.parametrize("k", [4, 6], ids=["2d", "3d"])
def test_temperature_scan_body_has_no_row_state_gather(one_chip, k):
    """At the cells' sizes the scan step reads and writes the per-row
    state by masked selects: no gather from, or scatter into, the (R, p)
    assignment or the (R, N, k) count state (flattened or not) is left in
    the loop body, and nothing of (R, 4k, p) is stored."""
    R, p, N = CELL_ROWS, CELL_P, CELL_NODES
    compiled = _temperature_kernel(200).lower(
        *_temperature_args(one_chip, R, p, N, k)).compile()
    row_state = [(op, n) for op, n in _while_body_ops(compiled.as_text())
                 if n in (R * p, R * N * k)]
    assert row_state == []
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


def test_stacked_counts_compile_for_v5e(one_chip):
    """The count-state seeding / rekeying kernel over 2K candidates."""
    grid = CartGrid(LADDER_DIMS)
    table = neighbor_table(grid, Stencil.nearest_neighbor(2))
    k, p = table.out_valid.shape
    compiled = _jit_stacked_counts(LADDER_PODS).lower(
        _shape((LADDER_ROWS, p), jnp.int32, one_chip),
        _shape((k, p), jnp.bool_, one_chip),
        _shape((k, p), jnp.int32, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("dims", [(32, 31), (31, 8, 4)], ids=["2d", "3d"])
def test_swap_scorer_compiles_for_v5e(one_chip, dims):
    """The polish's device swap scorer, one chunk of pairs, at the size of
    the paper's largest Fig. 8 instances (992 processes on 31 nodes)."""
    p, N, k = int(np.prod(dims)), 31, 2 * len(dims)
    i32, b = jnp.int32, jnp.bool_
    compiled = _scores_kernel().lower(
        _shape((p,), i32, one_chip), _shape((N, k), i32, one_chip),
        _shape((2, CHUNK), i32, one_chip), _shape((k,), i32, one_chip),
        _shape((k, p), b, one_chip), _shape((k, p), i32, one_chip),
        _shape((k, p), b, one_chip), _shape((k, p), i32, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("plan", ["hyperplane", "stencil_strips"])
def test_mapped_mesh_jacobi_compiles_for_v5e_2x2(topo, plan):
    """The four-chip path: a Jacobi over the 2x2 mesh ``cart_create``
    orders, 2048^2 per chip, halos exchanged by collective permutes."""
    cart = cart_create((2, 2), chips_per_pod=2, plan=plan, cache=False)
    mesh = Mesh(apply_layout(list(topo.devices), cart.layout),
                ("data", "model"))
    u = jax.ShapeDtypeStruct((2 * SHARD,) * 2, jnp.float32,
                             sharding=NamedSharding(mesh,
                                                    P("data", "model")))
    compiled = jacobi_sweeps(mesh, 8).lower(u).compile()
    assert "collective-permute" in compiled.as_text()
