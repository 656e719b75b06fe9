"""The SIMD-X processing engine: JIT task management + push-pull fused loops.

Paper mapping (Sec. 4-5):

  * push step  = frontier-driven edge expansion (load-balanced by a
    scatter of each vertex's first edge slot and a running max — the TPU
    replacement for thread/warp/CTA assignment over small/med/large
    worklists) + Compute + segment Combine + **online filter** for the
    next frontier.
  * pull step  = full-graph pass over the degree-bucketed ELL slices of the
    *in*-adjacency (each bucket = one workload class) + Compute + Combine +
    **ballot filter** (dense scan -> sorted unique frontier).
  * JIT controller = `lax.cond` on (overflow | frontier-edge volume) choosing
    the mode per iteration — online/push first, ballot/pull on overflow, and
    back (paper Fig. 7), generalized with the Beamer direction-optimizing
    volume test.
  * kernel fusion = `fusion='all'` puts both paths in ONE `lax.while_loop`
    body (one XLA executable, zero per-iteration dispatch — the fused
    persistent kernel); `fusion='pushpull'` uses two *specialized* inner loops
    so each body stays small (the paper's selective fusion that halves
    register pressure); `fusion='none'` dispatches one jitted step per
    iteration (the multi-kernel-launch baseline).

The global barrier the paper builds in software (deadlock-free via Eq. 1) is
inherited from XLA's `while` semantics; see DESIGN.md §2 for the resource
-accounting analogue used for Pallas block shapes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import frontier as F
from repro.core.acc import ACCProgram, Meta, gather_meta
from repro.graph.csr import CSR, EdgeDelta, Graph, live_degrees
from repro.graph.packing import EllPack

PUSH, PULL = jnp.int32(0), jnp.int32(1)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    frontier_cap: int                  # static frontier buffer (paper: thread bins)
    edge_cap: int                      # push-phase edge budget
    fusion: str = "all"                # 'none' | 'all' | 'pushpull'
    alpha: float = 0.15                # push->pull when frontier edges > alpha*|E|
    max_iters: int = 4096
    trace_len: int = 512               # mode trace for the Fig.8-style report
    pull_impl: str = "jnp"             # 'jnp' | 'pallas'
    sparse_combine: bool = False       # beyond-paper: O(E_f log E_f) push combine
    #: paper's online filter allows redundant entries (vote combiners); with
    #: static buffers dedupe keeps count == #vertices and avoids spurious
    #: overflow. False reproduces the paper's redundant-list behaviour.
    dedupe_online: bool = True
    #: frontier-aware masked pull (batched serving engine only): recompute an
    #: ELL row's partial only when one of its gathered senders is in some live
    #: lane's frontier, serving every other row from a loop-carried partial
    #: cache. Rows-to-recompute are stream-compacted into a bounded buffer of
    #: `ceil(rows * masked_pull_frac)` per slice; overflow falls back to the
    #: dense pull for that slice (the same static-buffer + overflow-bit
    #: resource accounting as the push edge budget, DESIGN.md §2/§8). Exact
    #: for min/max programs; for tol-thresholded programs (PPR) sub-tolerance
    #: drift outside the frontier is frozen, matching push-mode semantics.
    masked_pull: bool = False
    #: active-row buffer size per ELL slice, as a fraction of the slice's
    #: rows. Power-law graphs keep hub senders active deep into a run, so a
    #: generous budget (matching the measured hot-row tail) beats a tight one
    #: that overflows to dense every iteration.
    masked_pull_frac: float = 0.65
    #: edge-partitioned pools (serving/sharded.py): frontier-compact each
    #: shard's COO scan on light iterations — gather only the slots whose
    #: source is in the union frontier into a bounded `ceil(shard_slots *
    #: shard_compact_frac)` buffer, falling back to the dense per-shard scan
    #: when the consensus controller calls the iteration heavy or the buffer
    #: overflows (the same static-buffer + overflow-bit accounting as the
    #: push edge budget, DESIGN.md §2/§11). Results are bit-identical to the
    #: dense scan either way; this is purely a cost switch.
    shard_compact: bool = True
    #: compaction buffer size per edge shard, as a fraction of the shard's
    #: COO slots (delta lanes included).
    shard_compact_frac: float = 0.25


class EngineState(NamedTuple):
    m: Meta
    frontier: jnp.ndarray          # (cap,) int32, sentinel n
    count: jnp.ndarray             # int32
    fe_next: jnp.ndarray           # int32 — frontier out-degree volume
    mode: jnp.ndarray              # int32 PUSH/PULL
    overflow: jnp.ndarray          # bool
    it: jnp.ndarray                # int32
    done: jnp.ndarray              # bool
    push_iters: jnp.ndarray
    pull_iters: jnp.ndarray
    switches: jnp.ndarray
    mode_trace: jnp.ndarray        # (trace_len,) int8: 0 push, 1 pull, -1 unused
    #: (trace_len,) int32 — the frontier's out-edge volume ENTERING each
    #: iteration (the quantity the JIT controller decides on), -1 unused.
    #: Loop-carried like mode_trace: a bounded static buffer, no extra
    #: device work beyond one vector write per iteration, harvested with
    #: the final state (repro.obs per-iteration telemetry, DESIGN.md §12).
    fe_trace: jnp.ndarray


# ---------------------------------------------------------------------------
# frontier expansion (push): slot-balanced CSR gather
# ---------------------------------------------------------------------------


def _slot_owners(start: jnp.ndarray, deg: jnp.ndarray, edge_cap: int) -> jnp.ndarray:
    """Which frontier index owns each of `edge_cap` slots: every index with
    edges writes itself at its first slot `start`, and a running max carries
    it over its run. O(E + F), where a binary search per slot is O(E log F).
    `start`/`deg` may carry leading batch axes (vmapped). Slots before the
    first owner (only when no frontier vertex has an edge) read 0."""
    cap = start.shape[-1]
    if start.ndim > 1:
        owners = jax.vmap(lambda s, d: _slot_owners(s, d, edge_cap))(
            start.reshape((-1, cap)), deg.reshape((-1, cap)))
        return owners.reshape(start.shape[:-1] + (edge_cap,))
    tgt = jnp.where((deg > 0) & (start < edge_cap), start, edge_cap)
    mark = jnp.full((edge_cap + 1,), -1, jnp.int32)
    mark = mark.at[tgt].set(jnp.arange(cap, dtype=jnp.int32), mode="drop")
    return jnp.maximum(jax.lax.cummax(mark[:edge_cap]), 0)


def expand_frontier(csr: CSR, ids: jnp.ndarray, count: jnp.ndarray, edge_cap: int):
    """Expand the frontier's adjacency into a flat (edge_cap,) buffer with
    perfectly balanced lanes: lane e reads which frontier vertex owns edge e
    from a scatter of each vertex's first slot and a running max. Returns
    (src, dst, w, valid, total_edges).

    Batch-generic: `ids` may be (..., cap) with `count` (...,) — one
    independent frontier per leading row against the SHARED graph; all outputs
    then carry the same leading axes (query-major layouts). The unbatched path
    is unchanged and is what the vertex-major serving engine calls with its
    single union frontier (serving/batch_engine.py).
    """
    n = csr.n_nodes
    cap = ids.shape[-1]
    count = jnp.asarray(count)
    valid_v = jnp.arange(cap, dtype=jnp.int32) < count[..., None]
    safe = jnp.where(valid_v, jnp.minimum(ids, n - 1), 0)
    deg = jnp.where(valid_v, csr.row_ptr[safe + 1] - csr.row_ptr[safe], 0)
    cum = jnp.cumsum(deg, axis=-1)                         # inclusive
    if cap > 0:
        total = cum[..., -1]
    else:
        total = jnp.zeros(count.shape, jnp.int32)
    start = cum - deg                                      # exclusive
    e = jnp.arange(edge_cap, dtype=jnp.int32)
    owner = _slot_owners(start, deg, edge_cap)
    within = e - jnp.take_along_axis(start, owner, -1)
    src = jnp.take_along_axis(safe, owner, -1)
    ptr = jnp.minimum(csr.row_ptr[src] + within, csr.n_edges - 1)
    valid_e = e < jnp.minimum(total, edge_cap)[..., None]
    valid_e = jnp.broadcast_to(valid_e, src.shape)
    dst = jnp.where(valid_e, csr.col_idx[ptr], n)
    w = jnp.where(valid_e, csr.weights[ptr], 0.0)
    src = jnp.where(valid_e, src, n)
    return src, dst, w, valid_e, total


# ---------------------------------------------------------------------------
# one push / pull iteration
# ---------------------------------------------------------------------------


def _sparse_combine_apply(program, comb, m, upd, dst, n):
    """Beyond-paper push combine: sort the edge buffer by destination, fold
    each run with a segmented associative scan, and scatter ONE combined value
    per touched destination straight into the metadata — no (n+1) dense
    segment buffer. Inside the fused while_loop the scatter updates the
    loop-carried buffer in place, so the push iteration's write traffic is
    O(E_f), not O(|V|). Valid for idempotent default-apply programs
    (min/max monoids: BFS, SSSP, WCC, widest-path)."""
    primary = program.primary
    order = jnp.argsort(dst)                    # sentinel n sorts to the end
    sd = dst[order]
    su = upd[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sd[1:] != sd[:-1]])

    def op(a, b):
        va, fa = a
        vb, fb = b
        return jnp.where(fb, vb, comb.pair(va, vb)), fa | fb

    vals, _ = jax.lax.associative_scan(op, (su, first))
    last = jnp.concatenate([sd[1:] != sd[:-1], jnp.ones((1,), bool)])
    tgt = jnp.where(last, sd, n)                # only run-tails write
    base = m[primary]
    if comb.name == "min":
        newp = base.at[tgt].min(vals, mode="drop")
    else:
        newp = base.at[tgt].max(vals, mode="drop")
    newp = newp.at[-1].set(base[-1])            # keep scratch invariant
    out = dict(m)
    out[primary] = newp
    return out


def _push_step(program: ACCProgram, csr: CSR, cfg: EngineConfig, st: EngineState,
               delta: Optional[EdgeDelta] = None) -> EngineState:
    n = csr.n_nodes
    comb = program.combiner
    src, dst, w, valid_e, _total = expand_frontier(csr, st.frontier, st.count, cfg.edge_cap)
    if delta is not None:
        # streaming insertion overlay (DESIGN.md §8): the COO lanes are
        # appended to the expanded edge buffer unconditionally — sentinel
        # padding keeps unused lanes inert, so the solo push sees the
        # overlaid graph with zero shape changes (the pull path reads the
        # insertions from the delta slice appended to the ELL pack).
        src = jnp.concatenate([src, delta.src])
        dst = jnp.concatenate([dst, delta.dst])
        w = jnp.concatenate([w, delta.w])
        valid_e = jnp.concatenate([valid_e, delta.src < n])

    sender = gather_meta(st.m, src)
    receiver = gather_meta(st.m, dst)
    upd = program.compute(sender, w, receiver)
    ident = comb.identity(upd.dtype)
    upd = jnp.where(valid_e, upd, ident)

    if (cfg.sparse_combine and comb.idempotent and program.apply is None):
        m_new = _sparse_combine_apply(program, comb, st.m, upd, dst, n)
    else:
        seg = comb.segment(upd, dst, n + 1)
        # untouched lanes hold the identity already for min/max/sum monoids
        m_new = program.run_apply(st.m, seg, st.it)

    # online filter: per-edge activation, straight from the edge buffer
    new_d = gather_meta(m_new, dst)
    old_d = gather_meta(st.m, dst)
    changed_e = program.active(new_d, old_d, st.it) & valid_e
    if (not comb.idempotent) or cfg.dedupe_online:
        changed_e = F.dedupe_winners(changed_e, dst, n)
    ids, count, ovf = F.online_filter(changed_e, dst, cfg.frontier_cap, n)

    fe_next = _frontier_volume(csr, ids, count)
    return _advance(st, m_new, ids, count, fe_next, ovf, was_mode=PUSH)


def _pull_step(
    program: ACCProgram,
    pack: EllPack,
    cfg: EngineConfig,
    st: EngineState,
    csr_for_deg: CSR,
    pull_slice_fn: Optional[Callable] = None,
) -> EngineState:
    n = pack.n_nodes  # static python int (EllPack aux data)
    comb = program.combiner
    seg = jnp.full((n + 1,), comb.identity(st.m[program.primary].dtype))
    for s in pack.slices:
        if pull_slice_fn is not None:
            partial = pull_slice_fn(s, st.m[program.primary])
        else:
            sender = gather_meta(st.m, s.nbr)                       # (R, W) each
            recv = {k: v[s.row_id][:, None] for k, v in st.m.items()}
            upd = program.compute(sender, s.wgt, recv)
            ident = comb.identity(upd.dtype)
            upd = jnp.where(s.nbr == n, ident, upd)
            # tree reduce: association order pinned so batched serving runs
            # (serving/batch_engine.py, trailing query axis) stay bit-identical
            partial = comb.reduce_axis_tree(upd, axis=1)            # (R,)
        seg = comb.pair(seg, comb.segment(partial, s.row_id, n + 1))

    m_new = program.run_apply(st.m, seg, st.it)
    changed_v = program.active(m_new, st.m, st.it)
    changed_v = changed_v.at[-1].set(False)
    ids, count, ovf = F.ballot_filter(changed_v, cfg.frontier_cap, n)
    fe_next = _frontier_volume(csr_for_deg, ids, count)
    return _advance(st, m_new, ids, count, fe_next, ovf, was_mode=PULL)


def _frontier_volume(csr: CSR, ids: jnp.ndarray, count: jnp.ndarray) -> jnp.ndarray:
    """Frontier out-degree volume; batch-generic like `expand_frontier`."""
    n = csr.n_nodes
    count = jnp.asarray(count)
    valid = jnp.arange(ids.shape[-1], dtype=jnp.int32) < count[..., None]
    safe = jnp.where(valid, jnp.minimum(ids, n - 1), 0)
    deg = jnp.where(valid, csr.row_ptr[safe + 1] - csr.row_ptr[safe], 0)
    return jnp.sum(deg, axis=-1).astype(jnp.int32)


def _advance(st, m_new, ids, count, fe_next, ovf, was_mode) -> EngineState:
    it = st.it + 1
    slot = jnp.minimum(st.it, st.mode_trace.shape[0] - 1)
    tr = st.mode_trace.at[slot].set(was_mode.astype(jnp.int8))
    # st.fe_next is the volume that ENTERED the iteration just executed
    fe_tr = st.fe_trace.at[slot].set(st.fe_next)
    return EngineState(
        m=m_new,
        frontier=ids,
        count=count,
        fe_next=fe_next,
        mode=st.mode,  # decided in _policy
        overflow=ovf,
        it=it,
        done=st.done,
        push_iters=st.push_iters + jnp.where(was_mode == PUSH, 1, 0).astype(jnp.int32),
        pull_iters=st.pull_iters + jnp.where(was_mode == PULL, 1, 0).astype(jnp.int32),
        switches=st.switches,
        mode_trace=tr,
        fe_trace=fe_tr,
    )


def _policy(program: ACCProgram, cfg: EngineConfig, n_edges: int, st: EngineState) -> EngineState:
    """JIT controller (paper Fig. 7 + direction-optimizing volume test)."""
    if program.modes == "push":
        want = PUSH
    elif program.modes == "pull":
        want = PULL
    else:
        heavy = (
            st.overflow
            | (st.fe_next > jnp.int32(cfg.alpha * n_edges))
            | (st.fe_next > cfg.edge_cap)
        )
        want = jnp.where(heavy, PULL, PUSH)
    switched = (want != st.mode).astype(jnp.int32)
    max_it = program.fixed_iters if program.fixed_iters is not None else cfg.max_iters
    done = (st.count == 0) | (st.it >= max_it)
    return st._replace(mode=want, switches=st.switches + switched, done=done)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def init_state(program: ACCProgram, g: Graph, cfg: EngineConfig,
               delta: Optional[EdgeDelta] = None, **init_kw) -> EngineState:
    n = g.n_nodes
    # live degrees, not row_ptr diffs: on a streaming overlay the degree a
    # normalizing program (PageRank family) divides by must count the edges
    # actually traversed — deletion-neutralized slots out, delta COO in
    deg = live_degrees(g.out, delta)
    m0, f0 = program.init(n, deg, **init_kw)
    cap = cfg.frontier_cap
    if program.modes == "push":
        assert cap >= n and cfg.edge_cap >= g.n_edges, (
            "push-only programs must not overflow (set frontier_cap>=n, edge_cap>=m)"
        )
    # contract: init returns valid-first ids padded with sentinel n
    f0 = f0.astype(jnp.int32)
    total_valid = jnp.sum(f0 < n).astype(jnp.int32)
    k = min(int(f0.shape[0]), cap)
    ids = jnp.full((cap,), n, jnp.int32)
    ids = ids.at[:k].set(f0[:k])
    count = jnp.minimum(total_valid, k)
    st = EngineState(
        m=m0,
        frontier=ids,
        count=count,
        fe_next=jnp.int32(0),
        mode=PUSH,
        overflow=total_valid > k,
        it=jnp.int32(0),
        done=jnp.asarray(False),
        push_iters=jnp.int32(0),
        pull_iters=jnp.int32(0),
        switches=jnp.int32(0),
        mode_trace=jnp.full((cfg.trace_len,), -1, jnp.int8),
        fe_trace=jnp.full((cfg.trace_len,), -1, jnp.int32),
    )
    st = st._replace(fe_next=_frontier_volume(g.out, st.frontier, st.count))
    return _policy(program, cfg, g.n_edges, st)


def _make_step(program, g, pack, cfg, pull_slice_fn=None, delta=None):
    def step(st: EngineState) -> EngineState:
        st = jax.lax.cond(
            st.mode == PUSH,
            lambda s: _push_step(program, g.out, cfg, s, delta),
            lambda s: _pull_step(program, pack, cfg, s, g.out, pull_slice_fn),
            st,
        )
        return _policy(program, cfg, g.n_edges, st)

    return step


def make_pallas_pull(program: ACCProgram) -> Callable:
    """Build a per-slice pull implementation on the Pallas ELL kernel.

    Restriction (documented): Compute may only read the sender's primary
    field — true for the whole paper algorithm suite (the receiver dict is
    passed as a dummy).  The kernel template is instantiated from the user's
    ACC functions, mirroring how SIMD-X stamps its CUDA kernel templates.
    """
    from repro.kernels import ops as kops

    def compute1(v, w):
        return program.compute({program.primary: v}, w, {program.primary: v})

    def pull_slice_fn(s, vals):
        return kops.ell_combine(
            s.nbr, s.wgt, vals, compute1, combine=program.combiner.name
        )

    return pull_slice_fn


def run(
    program: ACCProgram,
    g: Graph,
    pack: EllPack,
    cfg: EngineConfig,
    pull_slice_fn: Optional[Callable] = None,
    delta=None,
    **init_kw,
):
    """Run an ACC program to convergence. Returns (metadata, stats dict).

    `delta` is a streaming :class:`~repro.graph.csr.EdgeDelta` insertion
    overlay (DESIGN.md §8): its COO lanes ride along the push edge buffer,
    so a solo run over a `StreamingGraph`'s views
    (`run(p, sg.graph, sg.pack, cfg, delta=sg.delta, ...)`) sees insertions
    without a CSR rebuild — bit-identical to the rebuilt graph for the
    monotone programs (tests/test_streaming.py pins it). Delta lanes
    contribute every push iteration regardless of the frontier — the same
    contract the pull path's delta ELL slice already imposes: an ACC
    program's inactive senders must message the combine identity or be
    absorbed idempotently (true for the whole suite: min/max relaxations,
    thresholded `send` fields, zero-when-stable aggregations).
    """
    if pull_slice_fn is None and cfg.pull_impl == "pallas":
        pull_slice_fn = make_pallas_pull(program)
    st0 = init_state(program, g, cfg, delta=delta, **init_kw)
    if cfg.fusion == "all":
        final = _run_fused_all(program, g, pack, cfg, st0, pull_slice_fn, delta)
    elif cfg.fusion == "pushpull":
        final = _run_fused_pushpull(program, g, pack, cfg, st0, pull_slice_fn,
                                    delta)
    elif cfg.fusion == "none":
        final = _run_unfused(program, g, pack, cfg, st0, pull_slice_fn, delta)
    else:
        raise ValueError(cfg.fusion)
    stats = {
        "iterations": final.it,
        "push_iters": final.push_iters,
        "pull_iters": final.pull_iters,
        "switches": final.switches,
        "mode_trace": final.mode_trace,
        "fe_trace": final.fe_trace,
        "final_count": final.count,
    }
    return final.m, stats


@functools.partial(jax.jit, static_argnums=(0, 3, 5))
def _run_fused_all(program, g, pack, cfg, st0, pull_slice_fn, delta=None):
    """One `lax.while_loop`, push+pull both resident ('all fusion')."""
    step = _make_step(program, g, pack, cfg, pull_slice_fn, delta)
    return jax.lax.while_loop(lambda s: ~s.done, step, st0)


@functools.partial(jax.jit, static_argnums=(0, 3, 5))
def _run_fused_pushpull(program, g, pack, cfg, st0, pull_slice_fn, delta=None):
    """Outer loop of two *specialized* inner loops (the paper's selective
    push-pull fusion): each inner body contains only one direction's code."""

    def push_only(st):
        st = _push_step(program, g.out, cfg, st, delta)
        return _policy(program, cfg, g.n_edges, st)

    def pull_only(st):
        st = _pull_step(program, pack, cfg, st, g.out, pull_slice_fn)
        return _policy(program, cfg, g.n_edges, st)

    def outer_body(st):
        st = jax.lax.while_loop(
            lambda s: (~s.done) & (s.mode == PUSH), push_only, st
        )
        st = jax.lax.while_loop(
            lambda s: (~s.done) & (s.mode == PULL), pull_only, st
        )
        return st

    return jax.lax.while_loop(lambda s: ~s.done, outer_body, st0)


def _run_unfused(program, g, pack, cfg, st0, pull_slice_fn, delta=None):
    """No fusion: one device dispatch per kernel per iteration (the paper's
    multi-kernel baseline, up to 40k launches)."""
    push = jax.jit(lambda s, d: _policy(program, cfg, g.n_edges,
                                        _push_step(program, g.out, cfg, s, d)))
    pull = jax.jit(lambda s: _policy(program, cfg, g.n_edges,
                                     _pull_step(program, pack, cfg, s, g.out,
                                                pull_slice_fn)))
    st = st0
    while not bool(st.done):
        st = push(st, delta) if int(st.mode) == 0 else pull(st)
    return st
