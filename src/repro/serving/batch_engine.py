"""Batched multi-query ACC engine: Q independent point queries, one fused loop.

The single-query engine (`core/engine.py`) runs ONE frontier through a
`lax.while_loop`. Serving traffic is many concurrent point queries (BFS/SSSP
from arbitrary sources, per-user PPR) against a SHARED graph. This module
stacks Q query states and advances all of them in one fused push-pull loop —
SIMD-X's JIT task management lifted from vertices to queries, in the
multi-source masked-SpMV/SpMM formulation of GraphBLAST (arXiv 1908.01407)
and the batched-traversal spirit of Gunrock (arXiv 1701.01170).

Layout is **vertex-major**: metadata fields are (n+1, Q) with the query axis
LAST, and the per-query frontier is a dense (n+1, Q) boolean mask. That
choice is what makes batching pay on real hardware (DESIGN.md §7):

  * Every graph-indexed gather (`m[nbr]`, `m[src]`) pulls CONTIGUOUS
    Q-vectors per vertex — one shared index stream serves all queries, so
    the irregular-access cost of a traversal is amortized Q ways instead of
    being repeated per query (this is exactly SpMV -> SpMM).
  * Segment combines run over the LEADING axis with (E, Q) payloads — the
    native `jax.ops.segment_*` path, one wide scatter; a query-major layout
    would need vmapped scatters, which XLA serializes.
  * **Union push**: in push mode the frontiers of all live queries are
    OR-ed, compacted ONCE with the unbatched online/ballot machinery, and
    expanded ONCE; per-edge updates are masked per query. JIT task
    management happens on the union, amortized across the batch.
  * **Consensus JIT controller**: one scalar push/pull decision per
    iteration from the aggregate union-frontier volume (paper Fig. 7 over
    the whole batch) — `lax.cond` on a batched predicate would execute both
    branches.
  * **Done-masking**: converged queries contribute nothing (their mask
    lanes are False and their metadata is frozen) instead of blocking the
    batch; the scheduler recycles their lanes mid-flight.

Exactness: for idempotent min/max programs (BFS, SSSP, WCC) a push and a
pull iteration compute identical metadata — every contribution is either
pushed when its sender changes or pulled from an already-final value, and
min/max are reassociation-free — so per-query results are bit-identical to
a solo `core.engine.run` even when the consensus mode sequence differs from
the solo policy's. Pull-only programs (PageRank, PPR) keep an identical
iteration structure by construction. Non-idempotent sum programs under
`modes='both'` match up to FP reassociation across modes.

Supported programs: `init` must accept a per-query `source=` kwarg (BFS,
SSSP, PPR) or be source-free, and `apply`/`active` must be elementwise in
the vertex axis (true for the whole paper suite except BP's iteration-count
`active`).
"""

from __future__ import annotations

import functools
import inspect
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import frontier as F
from repro.core.acc import ACCProgram
from repro.core.engine import PULL, PUSH, EngineConfig, expand_frontier
from repro.graph.csr import CSR, EdgeDelta, Graph, live_degrees
from repro.graph.packing import EllPack
from repro.obs import (
    TELE_LEN,
    TELE_MASKED_DENSE,
    TELE_MASKED_ROWS,
    TELE_PULL_EDGES,
    TELE_PUSH_EDGES,
)


class GraphDims(NamedTuple):
    """Static graph dimensions, standing in for a full :class:`Graph` on the
    CSR-free admission path (DESIGN.md §11): edge-partitioned pools never
    scan a replicated CSR, so their `init`/`_admit_lane` calls pass these
    dims plus the pool's cached (n,) live-degree vector instead of shipping
    the O(m) adjacency arrays into every admission."""

    n_nodes: int
    n_edges: int


class BatchState(NamedTuple):
    """Q stacked query states, vertex-major, plus one consensus mode."""

    m: dict                        # {field: (n+1, Q)}
    active: jnp.ndarray            # (n+1, Q) bool — frontier mask, scratch row False
    count: jnp.ndarray             # (Q,) int32 — per-query frontier size
    union_fe: jnp.ndarray          # () int32 — union-frontier out-edge volume
    overflow: jnp.ndarray          # () bool — union compaction overflowed
    mode: jnp.ndarray              # (Q,) int32 — mode each live lane last ran
    it: jnp.ndarray                # (Q,) int32
    done: jnp.ndarray              # (Q,) bool
    push_iters: jnp.ndarray        # (Q,) int32
    pull_iters: jnp.ndarray        # (Q,) int32
    switches: jnp.ndarray          # (Q,) int32
    mode_trace: jnp.ndarray        # (Q, trace_len) int8
    gmode: jnp.ndarray             # () int32 consensus PUSH/PULL
    #: masked-pull partial cache (cfg.masked_pull only): one (R_s, Q) array
    #: per ELL slice holding the slice's last computed row partials.
    pseg: tuple = ()
    #: () bool — next pull must run dense (init / admission / after a push
    #: invalidated the partial cache). None when masked pull is off.
    pull_dense: Optional[jnp.ndarray] = None
    #: (n+1, Q) bool — senders whose PRIMARY changed last iteration, the
    #: exact staleness set for the masked-pull partial cache. Carried only
    #: for residual-push programs (cfg.masked_pull + params kind='residual'),
    #: whose frontier does NOT cover every primary change (a vertex that
    #: absorbs its residual leaves the frontier while its `send` drops to
    #: zero) — with it the masked pull is BIT-IDENTICAL to the dense pull,
    #: not tol-bounded (DESIGN.md §10). None otherwise: min/max programs'
    #: frontiers already capture every change, and the tol-thresholded pull
    #: programs (ppr/pagerank) keep the documented frozen-drift semantics.
    hot: Optional[jnp.ndarray] = None
    #: (TELE_LEN + n_shards,) int32 — cumulative engine telemetry counters
    #: (edges scanned per direction, masked-pull / shard-compaction fallback
    #: events; layout in repro/obs/__init__.py) followed by the per-shard
    #: scan-volume plane (cumulative edges scanned by each shard; one slot
    #: on a single device). None when telemetry is off
    #: (`init_batch(telemetry=False)`, the default): the loop then carries
    #: no extra state and executes no extra ops — the telemetry-disabled
    #: overhead guard in tests/test_obs.py pins this.
    tele: Optional[jnp.ndarray] = None


def _ident(program: ACCProgram, m: dict):
    return program.combiner.identity(m[program.primary].dtype)


def _accepts_source(program: ACCProgram) -> bool:
    """Whether `program.init` takes a per-query `source=` kwarg."""
    params = inspect.signature(program.init).parameters
    return "source" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    )


def _apply_and_refilter(program, cfg, csr, st, seg):
    """Shared tail of a push/pull iteration: apply the combined updates, take
    the dense changed-mask as the next frontier (ballot semantics — the set a
    solo run's online/ballot filter would produce), and re-aggregate volumes."""
    with jax.named_scope("apply"):
        m_new = program.run_apply(st.m, seg, st.it)
        nxt = program.active(m_new, st.m, st.it)
        nxt = nxt.at[-1].set(False)                  # scratch row stays inert
        nxt = nxt & ~st.done[None, :]                # done lanes push nothing
        count = jnp.sum(nxt, axis=0).astype(jnp.int32)
        union_fe, overflow = _union_volume(csr, cfg, nxt)
        hot = None
        if st.hot is not None:
            # exact masked-pull staleness: a cached row partial goes stale
            # iff a gathered sender's primary changed this iteration (done
            # lanes are frozen by _advance, so they cannot change)
            hot = (m_new[program.primary] != st.m[program.primary]) \
                & ~st.done[None, :]
    return m_new, nxt, count, union_fe, overflow, hot


def _union_volume_deg(deg: jnp.ndarray, cfg: EngineConfig, mask: jnp.ndarray):
    """`_union_volume` from a bare (n,) out-degree vector — the form shared
    with the sharded engines, which carry degrees instead of a full CSR."""
    union = jnp.any(mask, axis=-1)                   # (n+1,)
    fe = jnp.sum(jnp.where(union[:-1], deg, 0)).astype(jnp.int32)
    ucount = jnp.sum(union[:-1]).astype(jnp.int32)
    return fe, ucount > cfg.frontier_cap


def _union_volume(csr: CSR, cfg: EngineConfig, mask: jnp.ndarray):
    """Out-edge volume of the union frontier + would-the-union-overflow."""
    return _union_volume_deg(csr.row_ptr[1:] - csr.row_ptr[:-1], cfg, mask)


# ---------------------------------------------------------------------------
# one batched push / pull iteration
#
# Each phase runs under a `jax.named_scope`, so a profile names the device
# time of every operation by phase (op_name `.../push/expand/...`): `push`
# (`compact`, `expand`, `compute`), `pull` (`slices`), `combine`, `apply`
# and `policy`. Scopes change only the operations' metadata.
# ---------------------------------------------------------------------------


def _push_step(program: ACCProgram, csr: CSR, cfg: EngineConfig, st: BatchState,
               delta: Optional[EdgeDelta] = None) -> BatchState:
    """Union-frontier push: ONE compaction + ONE balanced edge expansion for
    the whole batch (shared src/dst/w streams), per-query masking on the
    (E, Q) update matrix, one leading-axis segment combine.

    With a streaming `delta` (DESIGN.md §8), the inserted-edge COO lanes are
    appended to the expanded edge buffer unconditionally — base CSR + delta
    overlay feed ONE segment combine, and sentinel padding keeps unused lanes
    inert — so the push path sees the overlaid graph without a CSR rebuild.
    """
    with jax.named_scope("push"):
        n = csr.n_nodes
        comb = program.combiner
        with jax.named_scope("compact"):
            union = jnp.any(st.active, axis=-1)
            uids, ucount, _uovf = F.compact_mask(union[:n], cfg.frontier_cap,
                                                 fill=n)
        with jax.named_scope("expand"):
            src, dst, w, valid_e, _total = expand_frontier(
                csr, uids, ucount, cfg.edge_cap)
            if delta is not None:
                src = jnp.concatenate([src, delta.src])
                dst = jnp.concatenate([dst, delta.dst])
                w = jnp.concatenate([w, delta.w])
                valid_e = jnp.concatenate([valid_e, delta.src < n])

        with jax.named_scope("compute"):
            sender = {k: v[src] for k, v in st.m.items()}    # (E, Q) gathers
            receiver = {k: v[dst] for k, v in st.m.items()}
            upd = program.compute(sender, w[:, None], receiver)
            ident = comb.identity(upd.dtype)
            # an edge carries query q's message iff its source is in q's
            # frontier
            eactive = st.active[src] & valid_e[:, None]
            upd = jnp.where(eactive, upd, ident)
        with jax.named_scope("combine"):
            seg = comb.segment(upd, dst, n + 1)              # (n+1, Q)

        tele = st.tele
        if tele is not None:
            scanned = jnp.minimum(_total, jnp.int32(cfg.edge_cap))
            if delta is not None:
                scanned = scanned + jnp.sum(delta.src < n).astype(jnp.int32)
            tele = tele.at[TELE_PUSH_EDGES].add(scanned)

        m_new, nxt, count, fe, ovf, hot = _apply_and_refilter(
            program, cfg, csr, st, seg)
        return _advance(st, m_new, nxt, count, fe, ovf, was_mode=PUSH,
                        cfg=cfg, hot=hot, tele=tele)


def _slice_partial_dense(program, comb, m, s, n, ident):
    """One ELL slice's (R, Q) row partials, every row recomputed."""
    sender = {k: v[s.nbr] for k, v in m.items()}                 # (R, W, Q)
    recv = {k: v[s.row_id][:, None, :] for k, v in m.items()}
    upd = program.compute(sender, s.wgt[..., None], recv)
    upd = jnp.where(s.nbr[..., None] == n, ident, upd)
    return comb.reduce_axis_tree(upd, axis=1)                    # (R, Q)


def _slice_partial_masked(program, comb, m, s, n, ident, hot_v, prev,
                          force_dense, cfg):
    """Frontier-aware masked pull for one slice (cfg.masked_pull).

    A row's partial can only change if one of its gathered senders changed
    last iteration (`hot_v`, the union frontier mask) — everything else is
    served from the loop-carried cache `prev`. Hot rows are stream-compacted
    into a bounded `capR` row buffer (the pull analogue of the push edge
    budget); overflow or an invalidated cache falls back to the dense pull
    for this slice. Exact for min/max programs, whose `active` masks capture
    every value change; for tol-thresholded programs sub-tolerance drift
    outside the frontier stays frozen (push-mode semantics).

    Returns (partial, dense_taken, rows_recomputed) — the trailing pair
    feeds the telemetry accumulator (ignored when telemetry is off; both
    are byproducts of values this function computes anyway).
    """
    r, w = s.nbr.shape
    capR = min(r, max(8, int(math.ceil(r * cfg.masked_pull_frac))))
    hot = jnp.any(hot_v[s.nbr], axis=1)                          # (R,)
    ids, cnt, ovf = F.compact_mask(hot, capR, fill=r)

    def dense(_prev):
        return _slice_partial_dense(program, comb, m, s, n, ident)

    def sparse(prev):
        safe = jnp.minimum(ids, r - 1)
        nbr_sel = s.nbr[safe]                                    # (capR, W)
        rid_sel = s.row_id[safe]
        sender = {k: v[nbr_sel] for k, v in m.items()}           # (capR, W, Q)
        recv = {k: v[rid_sel][:, None, :] for k, v in m.items()}
        upd = program.compute(sender, s.wgt[safe][..., None], recv)
        upd = jnp.where(nbr_sel[..., None] == n, ident, upd)
        p_sel = comb.reduce_axis_tree(upd, axis=1)               # (capR, Q)
        # invalid lanes land on a dummy row; `ids` are unique by construction
        tgt = jnp.where(jnp.arange(capR, dtype=jnp.int32) < cnt, ids, r)
        buf = jnp.concatenate([prev, jnp.zeros((1, prev.shape[1]), prev.dtype)])
        return buf.at[tgt].set(p_sel)[:r]

    dense_taken = ovf | force_dense
    rows = jnp.where(dense_taken, jnp.int32(r), cnt)
    return jax.lax.cond(dense_taken, dense, sparse, prev), dense_taken, rows


def _pull_step(
    program: ACCProgram, pack: EllPack, cfg: EngineConfig, st: BatchState, csr_for_deg: CSR
) -> BatchState:
    """Full-graph pull over the degree-bucketed ELL slices, all queries at
    once: each slice's neighbor gather is (R, W, Q) with a contiguous query
    inner dim, reduced along the width then segment-combined per vertex.
    A streaming delta rides along as one more (static-shape) slice appended
    to the pack, so insertions need no special casing here."""
    with jax.named_scope("pull"):
        n = pack.n_nodes
        comb = program.combiner
        q = st.it.shape[0]
        ident = _ident(program, st.m)
        seg = jnp.full((n + 1, q), ident)
        # residual-push programs carry the exact changed-primary mask
        # (st.hot); everything else uses the union frontier (exact for
        # min/max, frozen sub-tol drift for thresholded pull programs)
        if not cfg.masked_pull:
            hot_v = None
        elif st.hot is not None:
            hot_v = jnp.any(st.hot, axis=-1)
        else:
            hot_v = jnp.any(st.active, axis=-1)
        pseg_new = []
        tele = st.tele
        with jax.named_scope("slices"):
            for si, s in enumerate(pack.slices):
                if cfg.masked_pull:
                    partial, dense_taken, rows = _slice_partial_masked(
                        program, comb, st.m, s, n, ident, hot_v, st.pseg[si],
                        st.pull_dense, cfg)
                    pseg_new.append(partial)
                    if tele is not None:
                        w = s.nbr.shape[1]
                        tele = (tele
                                .at[TELE_MASKED_DENSE].add(
                                    dense_taken.astype(jnp.int32))
                                .at[TELE_MASKED_ROWS].add(rows)
                                .at[TELE_PULL_EDGES].add(rows * jnp.int32(w)))
                else:
                    partial = _slice_partial_dense(program, comb, st.m, s, n,
                                                   ident)
                    if tele is not None:
                        tele = tele.at[TELE_PULL_EDGES].add(
                            jnp.int32(s.nbr.shape[0] * s.nbr.shape[1]))
                with jax.named_scope("combine"):
                    seg = comb.pair(seg,
                                    comb.segment(partial, s.row_id, n + 1))

        m_new, nxt, count, fe, ovf, hot = _apply_and_refilter(
            program, cfg, csr_for_deg, st, seg)
        return _advance(st, m_new, nxt, count, fe, ovf, was_mode=PULL,
                        cfg=cfg,
                        pseg=tuple(pseg_new) if cfg.masked_pull else None,
                        hot=hot, tele=tele)


def _advance(st, m_new, nxt, count, union_fe, overflow, was_mode, cfg=None,
             pseg=None, hot=None, tele=None) -> BatchState:
    live = ~st.done
    it = st.it + jnp.where(live, 1, 0)
    q = it.shape[0]
    tr_col = jnp.minimum(st.it, st.mode_trace.shape[-1] - 1)
    tr_val = jnp.where(live, jnp.int8(was_mode), st.mode_trace[jnp.arange(q), tr_col])
    tr = st.mode_trace.at[jnp.arange(q), tr_col].set(tr_val)
    keep = st.done[None, :]
    m_merged = {k: jnp.where(keep, st.m[k], m_new[k]) for k in st.m}
    # a pull leaves fresh partial caches; a push invalidates them
    pull_dense = st.pull_dense
    if cfg is not None and cfg.masked_pull:
        pull_dense = jnp.asarray(was_mode == PUSH)
    return st._replace(
        m=m_merged,
        active=nxt,
        count=jnp.where(live, count, jnp.int32(0)),
        union_fe=union_fe,
        overflow=overflow,
        it=it,
        push_iters=st.push_iters + jnp.where(live & (was_mode == PUSH), 1, 0),
        pull_iters=st.pull_iters + jnp.where(live & (was_mode == PULL), 1, 0),
        mode_trace=tr,
        pseg=st.pseg if pseg is None else pseg,
        pull_dense=pull_dense,
        hot=st.hot if hot is None else hot,
        tele=st.tele if tele is None else tele,
    )


# ---------------------------------------------------------------------------
# consensus policy
# ---------------------------------------------------------------------------


def _consensus_mode(program: ACCProgram, cfg: EngineConfig, n_edges: int, st) -> jnp.ndarray:
    """One scalar push/pull decision for the whole batch — the JIT controller
    (paper Fig. 7 + direction-optimizing volume test) over the union stream."""
    if program.modes == "push":
        return jnp.asarray(PUSH)
    if program.modes == "pull":
        return jnp.asarray(PULL)
    heavy = (
        st.overflow
        | (st.union_fe > jnp.int32(cfg.alpha * n_edges))
        | (st.union_fe > cfg.edge_cap)
    )
    return jnp.where(heavy, PULL, PUSH)


def _policy(program: ACCProgram, cfg: EngineConfig, n_edges: int, st: BatchState) -> BatchState:
    max_it = program.fixed_iters if program.fixed_iters is not None else cfg.max_iters
    with jax.named_scope("policy"):
        done = st.done | (st.count == 0) | (st.it >= max_it)
        live = ~done
        want = _consensus_mode(program, cfg, n_edges, st)
        switched = live & (want != st.mode)
        return st._replace(
            mode=jnp.where(live, want, st.mode),
            switches=st.switches + switched.astype(jnp.int32),
            done=done,
            gmode=jnp.asarray(want, jnp.int32),
        )


def make_batched_step(program: ACCProgram, g: Graph, pack: EllPack,
                      cfg: EngineConfig, delta: Optional[EdgeDelta] = None):
    """Per-iteration batched step (BatchState -> BatchState) — used by
    `run_batch`'s fused loop and by the scheduler's host-stepped loop.
    `delta` is the streaming insertion overlay for the push path; the pull
    path reads insertions from the delta slice appended to `pack`."""

    def step(st: BatchState) -> BatchState:
        if program.modes == "push":
            new = _push_step(program, g.out, cfg, st, delta)
        elif program.modes == "pull":
            new = _pull_step(program, pack, cfg, st, g.out)
        else:
            new = jax.lax.cond(
                st.gmode == PULL,
                lambda s: _pull_step(program, pack, cfg, s, g.out),
                lambda s: _push_step(program, g.out, cfg, s, delta),
                st,
            )
        if st.tele is not None and st.tele.shape[0] > TELE_LEN:
            # single-device per-shard plane: mirror this iteration's scan
            # volume into the (only) shard slot so tele[TELE_LEN:] always
            # equals the per-shard decomposition of the global counters
            inc = new.tele - st.tele
            scan = inc[TELE_PUSH_EDGES] + inc[TELE_PULL_EDGES]
            new = new._replace(tele=new.tele.at[TELE_LEN].add(scan))
        return _policy(program, cfg, g.n_edges, new)

    return step


# ---------------------------------------------------------------------------
# init / run
# ---------------------------------------------------------------------------


def init_batch(program: ACCProgram, g: Graph, cfg: EngineConfig,
               sources, done=None, pack: Optional[EllPack] = None,
               check_caps: bool = True,
               delta: Optional[EdgeDelta] = None,
               deg: Optional[jnp.ndarray] = None,
               telemetry: bool = False,
               tele_shards: int = 1) -> BatchState:
    """Stack Q fresh query states (one per source), vertex-major.

    `done` marks lanes to create as empty/inactive (the scheduler starts
    pools fully inactive and admits into lanes later). `pack` is required
    when `cfg.masked_pull` is set (the partial caches are sized per slice).
    `check_caps=False` skips the push-only no-overflow assertion for
    engines whose push path cannot truncate (the edge-partitioned scan,
    serving/sharded.py, never consults the frontier/edge budgets — its
    compaction buffer falls back to the dense shard scan on overflow).
    `delta` is the streaming insertion overlay — init only needs it for live
    degree counts (csr.live_degrees), so degree-normalizing programs see the
    overlaid topology's degrees; `deg` passes a precomputed live-degree
    vector instead (the O(m) count is constant per graph version, so the
    per-admission hot path supplies the pool's cached one rather than
    recounting every edge per admitted lane).

    `telemetry=True` seeds the cumulative `tele` counter vector (layout in
    repro/obs) that the steps then maintain; the default leaves `tele=None`
    — no extra loop-carried state, no extra ops (DESIGN.md §12).
    `tele_shards` sizes the trailing per-shard scan-volume plane
    (DESIGN.md §14): 1 on a single device, the 'data' extent for replicated
    pools, the 'model' extent for edge-sharded pools.

    `g` may be a bare :class:`GraphDims` (with `deg` required) on the
    CSR-free path: everything init computes from the adjacency — the union
    out-edge volume and the live degrees — then comes from `deg` alone, so
    edge-partitioned admissions never touch a replicated CSR. Note the two
    volume sources differ on an overlay: the CSR path counts row_ptr SLOTS
    (deletion-neutralized slots included), the deg path counts live edges —
    which is also what the edge-sharded loop body measures, so CSR-free
    pools see consistent volumes at admission and in-loop.
    """
    csr_free = isinstance(g, GraphDims)
    assert not csr_free or deg is not None, (
        "CSR-free init needs a precomputed live-degree vector")
    sources = jnp.asarray(sources, jnp.int32)
    q = sources.shape[0]
    n = g.n_nodes
    if program.modes == "push" and check_caps:
        # same no-overflow contract as engine.init_state: a push-only program
        # has no pull fallback, so a truncated union expansion would silently
        # drop updates (the consensus controller only reroutes modes='both').
        assert cfg.frontier_cap >= n and cfg.edge_cap >= g.n_edges, (
            "push-only programs must not overflow "
            "(set frontier_cap>=n, edge_cap>=m)"
        )
    if deg is None:
        deg = live_degrees(g.out, delta)
    if _accepts_source(program):
        m_q, f_q = jax.vmap(lambda s: program.init(n, deg, source=s))(sources)
        m = {k: v.T for k, v in m_q.items()}                 # (n+1, Q)
    else:
        # source-free program (e.g. global pagerank): one init, every lane
        # identical — sources are ignored.
        m_1, f_1 = program.init(n, deg)
        m = {k: jnp.broadcast_to(v[:, None], (n + 1, q)) for k, v in m_1.items()}
        f_q = jnp.broadcast_to(f_1[None, :], (q,) + f_1.shape)
    mask = jnp.zeros((n + 1, q), bool)
    lane = jnp.broadcast_to(jnp.arange(q, dtype=jnp.int32)[:, None], f_q.shape)
    mask = mask.at[f_q.astype(jnp.int32), lane].set(True, mode="drop")
    mask = mask.at[-1].set(False)
    if done is None:
        done = jnp.zeros((q,), bool)
    done = jnp.asarray(done)
    mask = mask & ~done[None, :]
    count = jnp.sum(mask, axis=0).astype(jnp.int32)
    if csr_free:
        union_fe, overflow = _union_volume_deg(deg, cfg, mask)
    else:
        union_fe, overflow = _union_volume(g.out, cfg, mask)
    if cfg.masked_pull and pack is not None:
        dt = m[program.primary].dtype
        ident = program.combiner.identity(dt)
        pseg = tuple(jnp.full((s.nbr.shape[0], q), ident) for s in pack.slices)
        pull_dense = jnp.asarray(True)
        # residual-push programs track exact staleness; start all-hot (the
        # first pull is dense anyway and refills every cached partial)
        hot = (jnp.ones((n + 1, q), bool)
               if program.param("kind") == "residual" else None)
    else:
        pseg, pull_dense, hot = (), None, None
    st = BatchState(
        m=m, active=mask, count=count, union_fe=union_fe, overflow=overflow,
        mode=jnp.full((q,), PUSH, jnp.int32),
        it=jnp.zeros((q,), jnp.int32),
        done=done | (count == 0),
        push_iters=jnp.zeros((q,), jnp.int32),
        pull_iters=jnp.zeros((q,), jnp.int32),
        switches=jnp.zeros((q,), jnp.int32),
        mode_trace=jnp.full((q, cfg.trace_len), -1, jnp.int8),
        gmode=jnp.asarray(PUSH, jnp.int32),
        pseg=pseg,
        pull_dense=pull_dense,
        hot=hot,
        tele=(jnp.zeros((TELE_LEN + int(tele_shards),), jnp.int32)
              if telemetry else None),
    )
    return st._replace(gmode=_consensus_mode(program, cfg, g.n_edges, st),
                       mode=jnp.where(st.done, st.mode,
                                      _consensus_mode(program, cfg, g.n_edges, st)))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _run_fused(program, g, pack, cfg, st0, delta=None):
    step = make_batched_step(program, g, pack, cfg, delta)
    return jax.lax.while_loop(lambda s: jnp.any(~s.done), step, st0)


def run_state(
    program: ACCProgram,
    g: Graph,
    pack: EllPack,
    cfg: EngineConfig,
    st0: BatchState,
    delta: Optional[EdgeDelta] = None,
    fusion: str = "all",
):
    """Advance an existing :class:`BatchState` to convergence. The streaming
    subsystem enters here with a state seeded from a previous fixpoint
    (incremental recomputation, DESIGN.md §8); `run_batch` enters with a
    fresh state. Returns (metadata dict, stats)."""
    if fusion == "all":
        final = _run_fused(program, g, pack, cfg, st0, delta)
    elif fusion == "none":
        step = jax.jit(make_batched_step(program, g, pack, cfg, delta))
        final = st0
        while bool(jnp.any(~final.done)):
            final = step(final)
    else:
        raise ValueError(fusion)
    stats = {
        "iterations": jnp.max(final.it),
        "per_query_iters": final.it,
        "push_iters": final.push_iters,
        "pull_iters": final.pull_iters,
        "switches": final.switches,
        "final_count": final.count,
        "mode_trace": final.mode_trace,
        "tele": final.tele,
    }
    return final.m, stats


def run_batch(
    program: ACCProgram,
    g: Graph,
    pack: EllPack,
    cfg: EngineConfig,
    sources,
    fusion: str = "all",
    delta: Optional[EdgeDelta] = None,
    telemetry: bool = False,
):
    """Run Q point queries of `program` (one per entry of `sources`) to
    convergence as one batch. Returns (metadata dict, field -> (n+1, Q),
    stats). `cfg.pull_impl`/`cfg.sparse_combine` are single-query fast paths
    and are ignored here. `telemetry=True` carries the cumulative engine
    counters (stats['tele'], layout in repro/obs)."""
    st0 = init_batch(program, g, cfg, sources, pack=pack, delta=delta,
                     telemetry=telemetry)
    return run_state(program, g, pack, cfg, st0, delta=delta, fusion=fusion)


def query_result(m: dict, field: str, lane: int) -> jnp.ndarray:
    """Extract lane `lane`'s (n,) result from vertex-major batched metadata."""
    return m[field][:-1, lane]


def run_sequential(program_factory, g: Graph, pack: EllPack, cfg: EngineConfig,
                   sources, run_fn=None):
    """Reference: the same queries one at a time through the single-query
    engine. Used by tests to assert bit-identity and by benchmarks as the
    no-batching baseline."""
    from repro.core import engine as E

    run_fn = run_fn or E.run
    outs = []
    for s in sources:
        m, _ = run_fn(program_factory(), g, pack, cfg, source=jnp.int32(int(s)))
        outs.append(m)
    return outs
