"""Pallas TPU kernel: ballot-filter stream compaction (paper Fig. 6b).

The GPU ballot filter does: coalesced scan of the metadata-changed mask,
`__ballot()` per warp, local rank via popcount-prefix, then each warp writes
its compacted ids.  The TPU version keeps the same two-level structure:

  kernel (this file): one grid step per block of `block` lanes — computes the
      lane prefix-sum of the mask (the vector analogue of ballot+popcount) and
      compacts the *global* vertex ids of set lanes to the front of the
      block's output row, emitting the block count;
  epilogue (ops.concat_blocks): exclusive scan over block counts + one gather
      concatenates blocks into the final **sorted, unique** frontier — the
      cheap cross-block step the paper does with a prefix-scan kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tuning import LANE


def _pack_kernel(mask_ref, ids_ref, cnt_ref, *, sentinel: int):
    b = mask_ref.shape[0]
    m = mask_ref[...].astype(jnp.int32)                    # (B,)
    pos = jnp.cumsum(m) - 1                                # lane rank
    gid0 = pl.program_id(0) * b
    gids = (jnp.arange(b, dtype=jnp.int32) + gid0)
    out = jnp.full((b + 1,), sentinel, jnp.int32)
    tgt = jnp.where(m > 0, pos, b)
    out = out.at[tgt].set(gids, mode="drop")
    ids_ref[...] = out[:b][None, :]
    cnt_ref[...] = jnp.full((1, LANE), jnp.sum(m), jnp.int32)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def frontier_pack(
    mask: jnp.ndarray, *, block: int = 1024, interpret: bool = True
):
    """mask (n,) bool, n % block == 0 -> (ids (nb, block), counts (nb,)).

    Each grid step writes lane-dense (1, block) and (1, 128) output blocks
    (its compacted ids, its count broadcast over one lane row); the wrapper
    reshapes them to the per-block views."""
    n = mask.shape[0]
    if n % block:
        raise ValueError(f"mask length {n} is not a multiple of block {block}")
    nb = n // block
    ids, cnt = pl.pallas_call(
        functools.partial(_pack_kernel, sentinel=n),
        grid=(nb,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, LANE), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, nb * LANE), jnp.int32),
        ],
        interpret=interpret,
    )(mask)
    return ids.reshape(nb, block), cnt.reshape(nb, LANE)[:, 0]


def concat_blocks(ids: jnp.ndarray, counts: jnp.ndarray, cap: int, sentinel: int):
    """XLA epilogue: flatten per-block compacted rows into one (cap,) frontier.
    Output stays sorted & unique because blocks are in vertex order."""
    nb, block = ids.shape
    offs = jnp.cumsum(counts) - counts                     # exclusive
    lane = jnp.broadcast_to(jnp.arange(block, dtype=jnp.int32), (nb, block))
    valid = lane < counts[:, None]
    tgt = jnp.where(valid, offs[:, None] + lane, cap)
    buf = jnp.full((cap + 1,), sentinel, jnp.int32)
    buf = buf.at[tgt.reshape(-1)].set(ids.reshape(-1), mode="drop")
    total = jnp.sum(counts)
    return buf[:cap], jnp.minimum(total, cap), total > cap
