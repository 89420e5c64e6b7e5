"""Pallas TPU kernel: k-neighborhood stencil apply (the paper's compute).

Computes ``out[i,j] = sum_k w_k * u[i + R_k0, j + R_k1]`` over a 2-d local
shard with an attached halo of width ``h`` (the halo is what the mapped
``MPI_Neighbor_alltoall`` analog exchanges; see examples/stencil_jacobi.py).

TPU adaptation: the CUDA-style version threads one point per thread; on TPU
the *output* is tiled over a 1-d grid of full-width row panels of
``tile_rows`` rows.  Output panel ``i`` needs input rows
``[i*tile_rows, (i+1)*tile_rows + 2h)``, so the haloed input is streamed
through two BlockSpecs on the same array: the panel itself (block ``i``)
and the ``tail`` rows below it (a sublane-aligned block of at least
``2h`` rows).  The kernel stacks both into one VMEM scratch panel and
reads the k shifted windows at *static* offsets — every dynamic index is
a block index, so Mosaic never has to prove a dynamic row offset aligned,
and VMEM holds a few panels rather than the whole shard.  The 3-d variant
below still keeps its input resident in VMEM, which bounds its shard size.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["stencil_kernel", "stencil_pallas", "stencil3d_kernel", "stencil3d_pallas"]

#: elements of one input panel (rows x haloed width): 2 MiB of f32, which
#: keeps the double-buffered panels, the scratch panel and the accumulator
#: of a 2048-wide shard inside the default scoped VMEM of a v5e core.
_PANEL_ELEMS = 1 << 19


def stencil_kernel(main_ref, tail_ref, out_ref, panel_ref, *, offsets,
                   weights, halo):
    """One grid step: compute a (tile_rows, W) output panel."""
    rows = main_ref.shape[0]
    panel_ref[:rows, :] = main_ref[...]
    panel_ref[rows:, :] = tail_ref[...]
    W = out_ref.shape[1]
    acc = None
    for (dy, dx), w in zip(offsets, weights):
        win = panel_ref[halo + dy:halo + dy + rows, halo + dx:halo + dx + W]
        term = win.astype(jnp.float32) * jnp.float32(w)
        acc = term if acc is None else acc + term
    out_ref[...] = acc.astype(out_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def stencil_pallas(u_halo: jnp.ndarray, offsets: Sequence[Tuple[int, int]],
                   weights: Sequence[float], halo: int,
                   tile_rows: Optional[int] = None,
                   interpret: bool = False) -> jnp.ndarray:
    """u_halo: (H + 2*halo, W + 2*halo) -> out: (H, W).

    ``tile_rows`` defaults to the largest power-of-two multiple of the tail
    block whose input panel stays within ``_PANEL_ELEMS``; an explicit
    value is rounded up to a multiple of the tail block."""
    H = u_halo.shape[0] - 2 * halo
    W = u_halo.shape[1] - 2 * halo
    Wh = u_halo.shape[1]
    # sublane tile of the dtype (8 rows of 32-bit, 16 of 16-bit), and a
    # tail block of whole tiles that covers the 2h halo rows
    sublanes = 8 * max(1, 4 // u_halo.dtype.itemsize)
    tail = _round_up(max(2 * halo, 1), sublanes)
    if tile_rows is None:
        tile_rows = tail
        while (2 * tile_rows * Wh <= _PANEL_ELEMS
               and tile_rows < _round_up(H, tail)):
            tile_rows *= 2
    tile_rows = _round_up(min(int(tile_rows), _round_up(H, tail)), tail)
    steps = pl.cdiv(H, tile_rows)
    # a tail block past the array's end holds no row a valid output row
    # reads (those all sit in the main panel then), so clamp it in range
    last_tail = pl.cdiv(H + 2 * halo, tail) - 1
    per = tile_rows // tail
    kern = functools.partial(stencil_kernel,
                             offsets=tuple(map(tuple, offsets)),
                             weights=tuple(float(w) for w in weights),
                             halo=halo)
    return pl.pallas_call(
        kern,
        grid=(steps,),
        in_specs=[pl.BlockSpec((tile_rows, Wh), lambda i: (i, 0)),
                  pl.BlockSpec((tail, Wh),
                               lambda i: (jnp.minimum((i + 1) * per,
                                                      last_tail), 0))],
        out_specs=pl.BlockSpec((tile_rows, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), u_halo.dtype),
        scratch_shapes=[pltpu.VMEM((tile_rows + tail, Wh), u_halo.dtype)],
        interpret=interpret,
    )(u_halo, u_halo)


def stencil3d_kernel(u_ref, out_ref, *, offsets, weights, halo, tile_z):
    """3-d variant: grid over z-slabs; each step reads the (tile_z + 2h)
    slab window and k statically-shifted (H, W) windows per z offset."""
    i = pl.program_id(0)
    z0 = i * tile_z
    H, W = out_ref.shape[1], out_ref.shape[2]
    acc = None
    for (dz, dy, dx), w in zip(offsets, weights):
        win = u_ref[pl.dslice(z0 + halo + dz, tile_z),
                    pl.dslice(halo + dy, H),
                    pl.dslice(halo + dx, W)]
        term = win.astype(jnp.float32) * jnp.float32(w)
        acc = term if acc is None else acc + term
    out_ref[pl.dslice(z0, tile_z), :, :] = acc.astype(out_ref.dtype)


def stencil3d_pallas(u_halo: jnp.ndarray, offsets, weights, halo: int,
                     tile_z: int = 4, interpret: bool = False) -> jnp.ndarray:
    """u_halo: (D+2h, H+2h, W+2h) -> out: (D, H, W)."""
    D = u_halo.shape[0] - 2 * halo
    H = u_halo.shape[1] - 2 * halo
    W = u_halo.shape[2] - 2 * halo
    if D % tile_z:
        tile_z = 1
    kern = functools.partial(stencil3d_kernel,
                             offsets=tuple(map(tuple, offsets)),
                             weights=tuple(float(w) for w in weights),
                             halo=halo, tile_z=tile_z)
    return pl.pallas_call(
        kern,
        grid=(D // tile_z,),
        in_specs=[pl.BlockSpec(u_halo.shape, lambda i: (0, 0, 0))],
        out_specs=pl.BlockSpec((D, H, W), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((D, H, W), u_halo.dtype),
        interpret=interpret,
    )(u_halo)
