"""Gradient-compression collectives (distributed-optimization tricks).

  * bf16 all-reduce with error feedback — halves DP all-reduce bytes; the
    quantization error is carried in a residual and re-injected next step, so
    the f32 master update stays unbiased over time.
  * top-k sparsified all-reduce (Deep Gradient Compression style) — each DP
    rank contributes its k largest-magnitude gradient entries; bytes go from
    2·|g| (ring all-reduce) to D·k·(4+4); wins for k/|g| < 1/D roughly.

Both are shard_map bodies over the 'data' axis; the train step applies them to
the microbatch-summed gradient before the optimizer.  Error-feedback residual
lives in the train state.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.compat import shard_map


def bf16_psum_ef(grad: jnp.ndarray, residual: jnp.ndarray, axis: str):
    """(inside shard_map) compress grad+residual to bf16, psum, return
    (reduced_f32, new_residual)."""
    want = grad.astype(jnp.float32) + residual
    sent = want.astype(jnp.bfloat16)
    new_res = want - sent.astype(jnp.float32)
    red = jax.lax.psum(sent.astype(jnp.float32), axis)
    return red, new_res


def topk_psum_ef(grad: jnp.ndarray, residual: jnp.ndarray, axis: str, k: int):
    """(inside shard_map) top-k magnitude sparsification with error feedback.
    Transfers 2k values+indices per rank via all_gather."""
    want = (grad.astype(jnp.float32) + residual).reshape(-1)
    mag = jnp.abs(want)
    vals, idx = jax.lax.top_k(mag, k)
    sel = want[idx]
    new_res = want.at[idx].set(0.0)
    g_idx = jax.lax.all_gather(idx, axis)            # (D, k)
    g_val = jax.lax.all_gather(sel, axis)            # (D, k)
    red = jnp.zeros_like(want).at[g_idx.reshape(-1)].add(g_val.reshape(-1))
    return red.reshape(grad.shape), new_res.reshape(grad.shape)


def make_compressed_allreduce(mesh: Mesh, axis: str, method: str = "bf16",
                              k_frac: float = 0.01):
    """Returns f(grad_tree, residual_tree) -> (reduced_tree, new_residual_tree)
    where grads are *per-DP-shard* partial gradients (shard_map over `axis`)."""

    def one(g, r):
        def body(gl, rl):
            if method == "bf16":
                return bf16_psum_ef(gl, rl, axis)
            k = max(1, int(gl.size * k_frac))
            return topk_psum_ef(gl, rl, axis, k)

        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=(P(axis), P(axis)),
            check_vma=False,
        )
        return fn(g, r)

    def apply(grads, residuals):
        flat_g, td = jax.tree.flatten(grads)
        flat_r = td.flatten_up_to(residuals)
        outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
        red = jax.tree.unflatten(td, [o[0] for o in outs])
        res = jax.tree.unflatten(td, [o[1] for o in outs])
        return red, res

    return apply
