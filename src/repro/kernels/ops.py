"""Public jit'd wrappers over the Pallas kernels, with an explicit XLA path.

Policy: on TPU backends the Pallas path compiles natively; on CPU
`interpret=True` executes the kernel bodies exactly for correctness
validation against ref.py; any other backend is an error. `use_xla=True`
selects the pure-XLA formulation (what the dry-run lowers for the production
mesh — Pallas TPU kernels cannot lower on the CPU dry-run backend, and the
XLA path is also the numerics oracle). Shapes a kernel cannot take raise;
they never switch to the XLA path behind the caller's back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import ell_spmv as _ell
from repro.kernels import embedding_bag as _bag
from repro.kernels import flash_attention as _fa
from repro.kernels import frontier_pack as _fp
from repro.kernels import segment_reduce as _sr


def default_interpret() -> bool:
    """Interpret on CPU, compile on TPU; no other backend runs these kernels."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on tpu or interpreted on cpu, "
            f"not on {backend!r}; pass use_xla=True for the XLA path")
    return backend == "cpu"


# -- ELL combine / SpMM ------------------------------------------------------


def ell_combine(nbr, wgt, vals, compute_fn, combine="min", use_xla=False,
                dead=None):
    if use_xla:
        if dead is not None:  # fold the deletion overlay before the ref path
            import jax.numpy as jnp

            nbr = jnp.where(dead != 0, vals.shape[0] - 1, nbr)
        return _ref.ell_combine_ref(nbr, wgt, vals, compute_fn, combine)
    return _ell.ell_combine(
        nbr, wgt, vals, dead, compute_fn=compute_fn, combine=combine,
        interpret=default_interpret(),
    )


def ell_spmm(nbr, wgt, feats, use_xla=False):
    if use_xla:
        return _ref.ell_spmm_ref(nbr, wgt, feats)
    return _ell.ell_spmm(nbr, wgt, feats, interpret=default_interpret())


# -- ballot-filter compaction ------------------------------------------------


def frontier_pack(mask, cap, block=1024, use_xla=False):
    n = mask.shape[0]
    if use_xla:
        from repro.core.frontier import compact_mask

        return compact_mask(mask, cap, fill=n)
    ids, cnt = _fp.frontier_pack(mask, block=block, interpret=default_interpret())
    return _fp.concat_blocks(ids, cnt, cap, sentinel=n)


# -- segment reduce ----------------------------------------------------------


def segment_reduce(vals, seg_ids, num_segments, combine="sum", use_xla=False):
    if use_xla:
        return _ref.segment_reduce_ref(vals, seg_ids, num_segments, combine)
    if vals.ndim != 2:
        raise ValueError(
            f"segment_reduce kernel takes (E, D) values, got shape {vals.shape}")
    return _sr.segment_reduce(
        vals, seg_ids, num_segments=num_segments, combine=combine,
        interpret=default_interpret(),
    )


# -- embedding bag -----------------------------------------------------------


def embedding_bag(table, idx, mode="sum", use_xla=False):
    if use_xla:
        return _ref.embedding_bag_ref(table, idx, mode)
    return _bag.embedding_bag(table, idx, mode=mode, interpret=default_interpret())


# -- attention ---------------------------------------------------------------


def attention(q, k, v, causal=True, use_xla=False, block_q=None, block_kv=None):
    if use_xla:
        return _ref.attention_ref(q, k, v, causal=causal)
    return _fa.flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_kv=block_kv,
        interpret=default_interpret(),
    )
