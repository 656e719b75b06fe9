"""Pallas TPU kernel: degree-bucketed ELL gather -> Compute -> Combine.

This is the ACC hot path (paper Sec. 3.3 line 1-8): for one ELL bucket the
kernel performs, per packed row r,

    partial[r] = COMBINE_j  COMPUTE(vals[nbr[r, j]], wgt[r, j])

i.e. one *workload class* of the paper's thread/warp/CTA trio.  The engine
invokes one `pallas_call` per bucket (small width -> many rows per tile; huge
rows pre-split into virtual rows by `packing.py`) and merges virtual rows with
a cheap XLA segment combine.

TPU adaptation notes (DESIGN.md §2):
  * the vertex metadata array `vals` is held resident in VMEM for the whole
    grid (BlockSpec maps every step to block 0) — valid for the (n+1) <= ~4M
    scalar budgets we size in `tuning.py`; the block-partitioned two-level
    variant would bucket edges by destination block (documented, not needed
    at bench scale);
  * per-slot gathers become `jnp.take` over the resident VMEM block (vector
    dynamic-gather on Mosaic; interpret-exact on CPU);
  * tile rows are chosen by the Eq.-1-style VMEM calculator in tuning.py.

The kernel is built per (Compute, Combine) pair — mirroring how SIMD-X
instantiates its kernel templates from user ACC functions at compile time.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tuning

_IDENT = {
    "min": lambda dt: jnp.asarray(jnp.finfo(dt).max / 4, dt),
    "max": lambda dt: jnp.asarray(-jnp.finfo(dt).max / 4, dt),
    "sum": lambda dt: jnp.asarray(0, dt),
}

_ROWREDUCE = {
    "min": lambda x: jnp.min(x, axis=1),
    "max": lambda x: jnp.max(x, axis=1),
    "sum": lambda x: jnp.sum(x, axis=1),
}


def _divisor_tile(rows: int, want: int) -> int:
    """Largest multiple of 8 that divides `rows` and is <= want (packing pads
    row counts to multiples of 8, so 8 always divides)."""
    t = min(want, rows)
    t -= t % 8
    t = max(t, 8)
    while rows % t:
        t -= 8
    return t


def _lane_tile(rows: int, want: int) -> int:
    """Rows per tile for a kernel whose per-row results are written as one
    lane-dense (1, tile) output block: a multiple of the 128-lane width,
    <= want, and no larger than `rows` rounded up to a lane multiple."""
    return max(tuning.LANE, min(tuning.round_down(want, tuning.LANE),
                                tuning.round_up(rows, tuning.LANE)))


def _ell_kernel(nbr_ref, wgt_ref, vals_ref, out_ref, *, compute_fn, combine):
    nbr = nbr_ref[...]                      # (TR, W) int32
    wgt = wgt_ref[...]                      # (TR, W) f32
    vals = vals_ref[...]                    # (n+1,) f32, resident
    n_sent = vals.shape[0] - 1
    gathered = jnp.take(vals, jnp.minimum(nbr, n_sent), axis=0)
    upd = compute_fn(gathered, wgt)
    ident = _IDENT[combine](vals.dtype)
    upd = jnp.where(nbr == n_sent, ident, upd)
    out_ref[...] = _ROWREDUCE[combine](upd)[None, :]


def _ell_kernel_overlay(nbr_ref, wgt_ref, dead_ref, vals_ref, out_ref, *,
                        compute_fn, combine):
    """Base ELL + deletion overlay in ONE pass: dead slots collapse to the
    combine identity at gather time, so a streaming delta (DESIGN.md §8) needs
    only an (R, W) int8 mask resident next to the slice instead of a
    neutralized copy of nbr/wgt."""
    nbr = nbr_ref[...]
    wgt = wgt_ref[...]
    dead = dead_ref[...]                    # (TR, W) int8: 1 = deleted slot
    vals = vals_ref[...]
    n_sent = vals.shape[0] - 1
    gathered = jnp.take(vals, jnp.minimum(nbr, n_sent), axis=0)
    upd = compute_fn(gathered, wgt)
    ident = _IDENT[combine](vals.dtype)
    upd = jnp.where((nbr == n_sent) | (dead != 0), ident, upd)
    out_ref[...] = _ROWREDUCE[combine](upd)[None, :]


@functools.partial(
    jax.jit, static_argnames=("compute_fn", "combine", "tile_rows", "interpret")
)
def ell_combine(
    nbr: jnp.ndarray,
    wgt: jnp.ndarray,
    vals: jnp.ndarray,
    dead: jnp.ndarray | None = None,
    *,
    compute_fn: Callable,
    combine: str = "min",
    tile_rows: int | None = None,
    interpret: bool = True,
) -> jnp.ndarray:
    """partial (R,) for one ELL slice. `vals` must carry the scratch slot.

    `dead` (optional, (R, W) int8/bool) is the streaming deletion overlay:
    slots flagged dead contribute the combine identity, bit-identical to
    running the plain kernel on a sentinel-neutralized copy of the slice.

    The kernel writes each tile's row results as one lane-dense (1, tile)
    block of a (1, R_pad) output; rows are padded with sentinel slots up to
    a whole number of tiles and the padding is sliced off again.
    """
    r, w = nbr.shape
    tr = _lane_tile(r, tile_rows or tuning.ell_tile_rows(w, vals.shape[0]))
    r_pad = tuning.round_up(r, tr)
    pad = ((0, r_pad - r), (0, 0))
    operands = [jnp.pad(nbr, pad, constant_values=vals.shape[0] - 1),
                jnp.pad(wgt, pad)]
    if dead is None:
        kernel = _ell_kernel
    else:
        kernel = _ell_kernel_overlay
        operands.append(jnp.pad(dead.astype(jnp.int8), pad))
    tile = pl.BlockSpec((tr, w), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(kernel, compute_fn=compute_fn, combine=combine),
        grid=(r_pad // tr,),
        in_specs=[tile] * len(operands) + [
            pl.BlockSpec((vals.shape[0],), lambda i: (0,))],
        out_specs=pl.BlockSpec((1, tr), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, r_pad), vals.dtype),
        interpret=interpret,
    )(*operands, vals)
    return out[0, :r]


# ---------------------------------------------------------------------------
# feature-matrix variant: GNN aggregation  out[r] = sum_j w[r,j] * F[nbr[r,j]]
# ---------------------------------------------------------------------------


def _spmm_kernel(nbr_ref, wgt_ref, feats_ref, out_ref):
    nbr = nbr_ref[...]                      # (TR, W)
    wgt = wgt_ref[...]
    feats = feats_ref[...]                  # (n+1, D) resident; row n is zeros
    n_sent = feats.shape[0] - 1
    w = jnp.where(nbr == n_sent, 0.0, wgt)
    g = jnp.take(feats, jnp.minimum(nbr, n_sent), axis=0)   # (TR, W, D)
    out_ref[...] = jax.lax.dot_general(
        w[:, None, :], g,
        dimension_numbers=((( 2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )[:, 0, :]


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def ell_spmm(
    nbr: jnp.ndarray,
    wgt: jnp.ndarray,
    feats: jnp.ndarray,
    *,
    tile_rows: int | None = None,
    interpret: bool = True,
) -> jnp.ndarray:
    """Weighted-sum aggregation over one ELL slice for (n+1, D) features.
    The per-row weighted reduction is expressed as a batched (1, W) x (W, D)
    matmul so Mosaic places it on the MXU."""
    r, w = nbr.shape
    npad, d = feats.shape
    tr = tile_rows or tuning.spmm_tile_rows(w, d, npad)
    tr = _divisor_tile(r, tr)
    return pl.pallas_call(
        _spmm_kernel,
        grid=(r // tr,),
        in_specs=[
            pl.BlockSpec((tr, w), lambda i: (i, 0)),
            pl.BlockSpec((tr, w), lambda i: (i, 0)),
            pl.BlockSpec((npad, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tr, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), feats.dtype),
        interpret=interpret,
    )(nbr, wgt, feats)
