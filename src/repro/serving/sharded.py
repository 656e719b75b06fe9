"""Sharded multi-device batched serving engine (DESIGN.md §9).

The batched vertex-major engine (`serving/batch_engine.py`) runs Q point
queries in one fused loop on ONE device. This module lifts that loop onto a
('data', 'model') device mesh with `shard_map`, along the two scaling axes
the repo already has layouts for (Gunrock's multi-GPU split, GraphBLAST's
SpMM view):

  * **query-sharded** (`placement='replicated'`): queries are embarrassingly
    parallel, so the Q axis splits over the 'data' mesh axis and the
    graph/pack/delta views replicate. Each shard runs the unmodified batched
    push/pull iteration on its Q/D lanes; the only cross-shard state is the
    JIT controller's input: per-shard union masks are `psum`-reduced over
    'data' into the exact global union, so the one scalar push/pull decision
    per iteration is a pure function of the same volumes the single-device
    consensus controller sees — the global mode sequence (and hence the mode
    trace) is identical to the single-device batched engine's.

  * **edge-partitioned** (`placement='edge_sharded'`): for graphs whose edge
    set outgrows one device, `graph/partition.py`'s 1-D edge shards split
    over the 'model' axis while metadata replicates within each mesh row.
    Each shard scans ITS edge partition per iteration (frontier-masked for
    push-semantics programs, unmasked for pull-only programs — the SpMM
    formulation), segment-combines locally into an (n+1, Q) partial, and the
    partials merge across shards with the combine monoid's all-reduce
    (`psum` for sum — implementable as psum_scatter+all_gather — and
    pmin/pmax for the idempotent monoids). Per-iteration device state
    touches only the shard's E/S edge triples + O(n·Q) metadata. Round 2
    (DESIGN.md §11): LIGHT iterations frontier-compact the shard scan
    (`cfg.shard_compact` — gather only union-frontier slots into a bounded
    buffer, switched by the consensus controller, dense fallback on
    overflow, bit-identical either way); admission and init are CSR-FREE
    (only the cached (n,) live-degree vector, never the O(m) adjacency);
    streaming updates ship only the CHANGED per-shard slices / replicated
    leaves (`set_graph` diffing, `last_ship`).

Exactness (§7 argument, unchanged): per-query metadata is a pure function
of per-query frontier trajectories; batch-mates and shard layout influence
only the mode sequence, and for idempotent min/max programs a push and a
pull iteration produce bit-identical metadata. Query-sharded results are
therefore bit-identical to the single-device batched engine for the whole
served suite (pull-only sum programs trivially so: identical iteration
structure, pinned reduction trees). Edge-partitioned results are bit-exact
for min/max programs (min/max are reassociation-free across the shard
merge); sum programs see one extra reassociation (the cross-shard psum) and
match to FP tolerance.

Consensus flavors:

  * `consensus='global'` (default): the psum'd controller above. Shards run
    in lockstep (the fused loop carries the psum'd live count so every shard
    exits the `while_loop` on the same trip); the mode trace equals the
    single-device trace (tests/test_sharded.py pins this on RMAT-12).
  * `consensus='local'`: each shard decides modes from its own union — NO
    collectives at all in replicated placement, so shards converge fully
    independently (results still bit-identical by idempotence; mode traces
    may diverge per shard — the regression test demonstrates the divergence
    the psum reduction exists to prevent). Fused runs only.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import frontier as F
from repro.core.acc import ACCProgram, Combiner
from repro.core.engine import PULL, PUSH, EngineConfig
from repro.graph import partition
from repro.graph.csr import EdgeDelta, Graph, live_degrees
from repro.graph.packing import EllPack
from repro.obs import (
    TELE_COMPACT_DENSE,
    TELE_COMPACT_HITS,
    TELE_LEN,
    TELE_PULL_EDGES,
    TELE_PUSH_EDGES,
)
from repro.serving import batch_engine as B

DATA_AXIS = "data"     # query shards
MODEL_AXIS = "model"   # edge shards

_SPEC_LEAF = lambda x: isinstance(x, P) or x is None  # noqa: E731


def make_serving_mesh(n_query_shards: int = 1, n_edge_shards: int = 1):
    """('data', 'model') mesh for sharded pools. Needs
    `n_query_shards * n_edge_shards` jax devices of the default backend; the
    mesh takes the first `need` of them, one shard per device."""
    devs = jax.devices()
    need = n_query_shards * n_edge_shards
    if len(devs) < need:
        platform = devs[0].platform
        hint = (" (a CPU mesh takes XLA_FLAGS="
                "--xla_force_host_platform_device_count=N)"
                if platform == "cpu" else "")
        raise RuntimeError(
            f"mesh ({n_query_shards}, {n_edge_shards}) needs {need} devices, "
            f"found {len(devs)} {platform} device(s){hint}")
    return compat.make_mesh(
        (n_query_shards, n_edge_shards), (DATA_AXIS, MODEL_AXIS),
        devices=devs[:need],
        axis_types=(compat.AxisType.Auto, compat.AxisType.Auto),
    )


def state_specs(st: B.BatchState, mesh=None) -> B.BatchState:
    """PartitionSpec tree for a BatchState: Q axis over 'data', vertex axis
    and consensus scalars replicated (the global controller keeps the
    scalars bitwise-equal across shards). With a mesh, the specs come from
    the logical-axis layer (`distributed/sharding.py`'s 'queries' rule), so
    the state layout collapses gracefully on meshes without a 'data' axis."""
    if mesh is not None:
        from repro.distributed import sharding as SH

        with SH.activate(mesh):
            qv = SH.spec(None, "queries")   # (n+1, Q) vertex-major
            ql = SH.spec("queries")         # (Q,) per-lane
            tr = SH.spec("queries", None)   # (Q, trace_len)
    else:
        qv, ql, tr = P(None, DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS, None)
    return B.BatchState(
        m={k: qv for k in st.m},
        active=qv, count=ql, union_fe=P(), overflow=P(),
        mode=ql, it=ql, done=ql,
        push_iters=ql, pull_iters=ql, switches=ql,
        mode_trace=tr, gmode=P(),
        pseg=tuple(qv for _ in st.pseg),
        pull_dense=None if st.pull_dense is None else P(),
        hot=None if st.hot is None else qv,
        # cumulative telemetry counters are mesh-global (increments are
        # psum'd across shards inside the steps), hence replicated
        tele=None if st.tele is None else P(),
    )


def _replicated_specs(tree):
    return jax.tree.map(lambda _: P(), tree)


def _monoid_all_reduce(comb: Combiner, x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """All-reduce `x` across `axis` in the combine monoid. The idempotent
    monoids use pmin/pmax (reassociation-free -> bit-exact merge); sum uses
    psum (the psum_scatter + all_gather decomposition when XLA tiles it)."""
    if comb.name == "sum":
        return jax.lax.psum(x, axis)
    if comb.name == "min":
        return jax.lax.pmin(x, axis)
    if comb.name == "max":
        return jax.lax.pmax(x, axis)
    raise ValueError(comb.name)


def _global_union_volume(deg, cfg, mask, axis):
    """The single-device controller's (union_fe, overflow) reconstructed
    exactly across query shards: psum the per-shard union masks (union of
    unions, NOT a sum of volumes — overlapping frontiers must not double
    count), then measure the global union's out-edge volume."""
    local = jnp.any(mask, axis=-1).astype(jnp.int32)      # (n+1,)
    union = jax.lax.psum(local, axis) > 0
    fe = jnp.sum(jnp.where(union[:-1], deg, 0)).astype(jnp.int32)
    ucount = jnp.sum(union[:-1]).astype(jnp.int32)
    return fe, ucount > cfg.frontier_cap


def _live_count(st, axes) -> jnp.ndarray:
    live = jnp.sum(~st.done).astype(jnp.int32)
    for ax in axes:
        live = jax.lax.psum(live, ax)
    return live


def _normalize_scalars(st, comb_gmode_axes):
    """Deterministic consensus scalars at loop exit for flavors whose shards
    carry shard-local values (local consensus / per-row edge shards):
    aggregate volume, any-overflow, max mode — replicated by construction so
    the P() out_specs hold."""
    fe = jax.lax.psum(st.union_fe, comb_gmode_axes)
    ovf = jax.lax.psum(st.overflow.astype(jnp.int32), comb_gmode_axes) > 0
    gmode = jax.lax.pmax(st.gmode, comb_gmode_axes)
    st = st._replace(union_fe=fe, overflow=ovf, gmode=gmode)
    if st.tele is not None:
        # fused edge-sharded bodies keep tele 'data'-local (a psum over
        # 'data' inside the while_loop would deadlock: rows exit at
        # independent trip counts); globalize once here at loop exit.
        # Within a 'model' group the body already summed, so 'data' only.
        st = st._replace(tele=jax.lax.psum(st.tele, DATA_AXIS))
    return st


# ---------------------------------------------------------------------------
# per-shard step bodies
# ---------------------------------------------------------------------------


def _make_replicated_step(program: ACCProgram, cfg: EngineConfig,
                          n_edges: int, consensus: str):
    """One query-shard iteration: the unmodified single-device batched step
    on the shard's lanes, with the controller inputs globalized by psum when
    `consensus='global'`."""

    def step(st: B.BatchState, g: Graph, pack: EllPack,
             delta: Optional[EdgeDelta]) -> B.BatchState:
        if program.modes == "push":
            new = B._push_step(program, g.out, cfg, st, delta)
        elif program.modes == "pull":
            new = B._pull_step(program, pack, cfg, st, g.out)
        else:
            new = jax.lax.cond(
                st.gmode == PULL,
                lambda s: B._pull_step(program, pack, cfg, s, g.out),
                lambda s: B._push_step(program, g.out, cfg, s, delta),
                st,
            )
        if consensus == "global":
            # the psum sits OUTSIDE the push/pull cond: every shard executes
            # it unconditionally, so the collective schedule is uniform
            deg = g.out.row_ptr[1:] - g.out.row_ptr[:-1]
            fe, ovf = _global_union_volume(deg, cfg, new.active, DATA_AXIS)
            new = new._replace(union_fe=fe, overflow=ovf)
            if st.tele is not None:
                # the inner step added this shard's lanes' increments; the
                # carried accumulator is mesh-global (replicated spec), so
                # globalize the increment the same way as the controller
                # inputs — unconditional psum, uniform collective schedule
                inc = new.tele - st.tele
                if inc.shape[0] > TELE_LEN:
                    # per-shard plane: this 'data' row's scan volume lands
                    # in its own slot BEFORE the psum — the one-hot
                    # contributions assemble the full plane on every shard,
                    # reusing the collective the named counters already pay
                    scan = inc[TELE_PUSH_EDGES] + inc[TELE_PULL_EDGES]
                    slot = TELE_LEN + jax.lax.axis_index(DATA_AXIS)
                    inc = inc.at[slot].add(scan)
                inc = jax.lax.psum(inc, DATA_AXIS)
                new = new._replace(tele=st.tele + inc)
        return B._policy(program, cfg, n_edges, new)

    return step


def _make_edge_sharded_step(program: ACCProgram, cfg: EngineConfig,
                            n: int, n_edges: int,
                            tele_axes=(DATA_AXIS, MODEL_AXIS)):
    """One edge-shard iteration: scan the shard's COO partition (masked by
    the union frontier for push-semantics programs, unmasked for pull-only
    programs), segment-combine locally, monoid-all-reduce across 'model'.

    No edge budget, no truncation: heavy iterations scan every shard slot
    densely, so push-only programs run without the no-overflow capacity
    assertion and the mode controller degenerates to one scan KIND per
    program. Light iterations of push-semantics programs take the
    **frontier-compacted expansion** (`cfg.shard_compact`, DESIGN.md §11):
    the shard gathers only COO slots whose source is in the union frontier —
    stream-compacted into a bounded `ceil(slots * shard_compact_frac)`
    buffer — instead of paying the full O(m/shards) gather/compute. The
    existing consensus controller is the switch (its PUSH decision == a
    light iteration; pull-only programs always scan densely — every slot
    contributes to an unmasked SpMM), and a compaction-buffer overflow falls
    back to the dense scan for that iteration, so nothing can ever truncate.
    Both scan flavors produce the same contribution multiset per
    destination, so results (and the degenerate mode trace) are
    bit-identical to the always-dense scan — compaction is purely a cost
    switch, which is what lets the two paths share one differential test
    oracle (tests/test_sharded.py).
    """
    comb = program.combiner
    masked = program.modes != "pull"      # push semantics for both/push
    was_mode = PUSH if masked else PULL

    def scan_dense(st, src, dst, w, valid):
        sender = {k: v[src] for k, v in st.m.items()}        # (E_s, Q) rows
        receiver = {k: v[dst] for k, v in st.m.items()}
        upd = program.compute(sender, w[:, None], receiver)
        ident = comb.identity(upd.dtype)
        if masked:
            eactive = st.active[src] & valid[:, None]
        else:
            eactive = jnp.broadcast_to(valid[:, None], upd.shape)
        upd = jnp.where(eactive, upd, ident)
        return comb.segment(upd, dst, n + 1)                 # shard partial

    def scan_compacted(st, src, dst, w, eact, cap):
        # the id compaction (cumsum + scatter) runs only on iterations that
        # actually take this branch; heavy iterations pay one O(E_s) count
        ids, lane_ok, _ovf = F.select_edges(eact, cap)
        ssrc, sdst, sw = src[ids], dst[ids], w[ids]
        sender = {k: v[ssrc] for k, v in st.m.items()}       # (cap, Q) rows
        receiver = {k: v[sdst] for k, v in st.m.items()}
        upd = program.compute(sender, sw[:, None], receiver)
        ident = comb.identity(upd.dtype)
        # selected lanes hold union-frontier edges; per-query masking still
        # applies (an edge carries query q's message iff its source is in
        # q's frontier), and clamped filler lanes are inert
        eactive = st.active[ssrc] & lane_ok[:, None]
        upd = jnp.where(eactive, upd, ident)
        return comb.segment(upd, sdst, n + 1)

    def step(st: B.BatchState, esrc, edst, ewgt, deg,
             dsrc, ddst, dwgt) -> B.BatchState:
        src = esrc.reshape(-1)
        dst = edst.reshape(-1)
        w = ewgt.reshape(-1)
        if dsrc is not None:              # per-shard streaming delta slice
            src = jnp.concatenate([src, dsrc.reshape(-1)])
            dst = jnp.concatenate([dst, ddst.reshape(-1)])
            w = jnp.concatenate([w, dwgt.reshape(-1)])
        valid = (src < n) & (dst < n)     # sentinel pads / neutralized slots

        e_tot = int(src.shape[0])
        tele_inc = (None if st.tele is None
                    else jnp.zeros_like(st.tele))
        if masked and cfg.shard_compact:
            cap = min(e_tot, max(128, int(
                math.ceil(e_tot * cfg.shard_compact_frac))))
            union = jnp.any(st.active, axis=-1)              # (n+1,)
            eact = union[src] & valid
            c_ovf = jnp.sum(eact) > cap                      # O(E_s) count
            # the controller's carried decision: PUSH == light iteration.
            # Shards of one 'model' group see identical lanes, so they take
            # the same branch; the cross-shard all-reduce sits OUTSIDE the
            # cond, so divergent groups (possible when Q also shards over
            # 'data') still meet every collective in lockstep.
            heavy = B._consensus_mode(program, cfg, n_edges, st) == PULL
            seg = jax.lax.cond(
                heavy | c_ovf,
                lambda s: scan_dense(s, src, dst, w, valid),
                lambda s: scan_compacted(s, src, dst, w, eact, cap),
                st,
            )
            if tele_inc is not None:
                light = ~(heavy | c_ovf)                  # compacted branch
                tele_inc = (
                    tele_inc
                    .at[TELE_COMPACT_HITS].add(light.astype(jnp.int32))
                    .at[TELE_COMPACT_DENSE].add(
                        (~heavy & c_ovf).astype(jnp.int32))
                    # buffer lanes gathered vs full shard slots scanned
                    .at[TELE_PUSH_EDGES].add(
                        jnp.where(light, jnp.int32(cap), jnp.int32(e_tot))))
        else:
            seg = scan_dense(st, src, dst, w, valid)
            if tele_inc is not None:
                slot = TELE_PUSH_EDGES if masked else TELE_PULL_EDGES
                tele_inc = tele_inc.at[slot].add(jnp.int32(e_tot))
        seg = _monoid_all_reduce(comb, seg, MODEL_AXIS)      # cross-shard merge
        if tele_inc is not None:
            # each (data, model) shard counted its own slice's work.
            # Host-stepped bodies sum over BOTH axes (every shard steps
            # exactly once per call, and the replicated out-spec needs the
            # mesh-global value); fused-loop bodies sum over 'model' only —
            # data rows exit the while_loop at independent trip counts, so
            # a 'data' collective inside the loop would deadlock, and
            # `_normalize_scalars` globalizes at exit instead.
            # Unconditional collective (sits outside the cond above).
            if tele_inc.shape[0] > TELE_LEN:
                # per-shard plane: this 'model' column's slice volume lands
                # in its own slot before the existing psum — the plane then
                # resolves to per-edge-shard totals (summed over 'data' by
                # the same psum / the exit normalize) at zero extra
                # collectives
                scan = tele_inc[TELE_PUSH_EDGES] + tele_inc[TELE_PULL_EDGES]
                slot = TELE_LEN + jax.lax.axis_index(MODEL_AXIS)
                tele_inc = tele_inc.at[slot].add(scan)
            tele_inc = jax.lax.psum(tele_inc, tele_axes)

        m_new = program.run_apply(st.m, seg, st.it)
        nxt = program.active(m_new, st.m, st.it)
        nxt = nxt.at[-1].set(False)
        nxt = nxt & ~st.done[None, :]
        count = jnp.sum(nxt, axis=0).astype(jnp.int32)
        fe, ovf = B._union_volume_deg(deg, cfg, nxt)
        tele = None if tele_inc is None else st.tele + tele_inc
        new = B._advance(st, m_new, nxt, count, fe, ovf,
                         was_mode=was_mode, cfg=cfg, tele=tele)
        max_it = (program.fixed_iters if program.fixed_iters is not None
                  else cfg.max_iters)
        done = new.done | (new.count == 0) | (new.it >= max_it)
        return new._replace(done=done)

    return step


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class ShardedBatchEngine:
    """The batched ACC loop under shard_map on a ('data', 'model') mesh.

    `placement='replicated'` query-shards Q over 'data' with the graph
    replicated; `placement='edge_sharded'` splits the edge list over 'model'
    (queries still shard over 'data' when it is >1). Graph views are traced
    args placed once per `set_graph` — streaming updates swap views without
    recompiling, exactly like the single-device pools.
    """

    def __init__(self, program: ACCProgram, g: Graph, pack: EllPack,
                 cfg: EngineConfig, mesh, *, placement: str = "replicated",
                 consensus: str = "global",
                 delta: Optional[EdgeDelta] = None,
                 telemetry: bool = False):
        assert placement in ("replicated", "edge_sharded"), placement
        assert consensus in ("global", "local"), consensus
        if placement == "edge_sharded":
            assert not cfg.masked_pull, (
                "masked pull's per-slice caches assume a replicated pack")
        assert not (telemetry and consensus == "local"), (
            "telemetry counters are mesh-global (psum'd increments) — "
            "consensus='local' promises NO collectives, so the replicated "
            "accumulator spec cannot hold; run telemetry with "
            "consensus='global'")
        self.telemetry = bool(telemetry)
        self.program = program
        self.cfg = cfg
        self.mesh = mesh
        self.placement = placement
        self.consensus = consensus
        self.n = g.n_nodes
        self.n_edges = g.n_edges
        self.n_query_shards = int(mesh.shape[DATA_AXIS])
        self.n_edge_shards = int(mesh.shape[MODEL_AXIS])
        self._specs = None          # built on first init (needs a template)
        self._shardings = None
        self._step_j = None
        self._run_j = None
        self._rebuild_pending = False
        # diff-shipping caches (touched-delta slice shipping, DESIGN.md §11)
        self._rep_cache: dict = {}      # replicated: name -> (treedef, host, dev)
        self._row_cache: dict = {}      # edge-sharded: name -> (host (S,L), dev)
        self._base_leaves = None
        self._delta_leaves = None
        self.deg = None
        self._deg_base = None
        self.delta = delta              # pre-set for set_graph's delta-ness check
        self.last_ship: dict = {}
        self.set_graph(g, pack, delta)

    # -- device views --------------------------------------------------------

    def set_graph(self, g: Graph, pack: EllPack,
                  delta: Optional[EdgeDelta]) -> None:
        """(Re)place the graph views on the mesh, shipping only what CHANGED
        (DESIGN.md §11 — streaming updates used to re-broadcast every view to
        every replica per batch):

          * replicated placement diffs the new views against the previous
            ones LEAF BY LEAF (the streaming overlay keeps untouched arrays
            identity-stable across `apply` batches) and re-broadcasts only
            the changed leaves — an insert-only batch ships the delta COO +
            the delta ELL slice, never the O(m) CSR arrays;
          * edge-sharded placement re-slices and ships only the per-shard
            COO/delta ROWS whose contents changed (`partition.shard_delta`
            diffed against the previous slices), stitching unchanged shards'
            resident device buffers back into the global view. The O(m)
            adjacency itself never lands on the mesh at all — admission is
            CSR-free and consumes only the cached (n,) live-degree vector.

        Shapes are update-invariant, so pools swap views with no recompile
        (an overflow rebuild changes m and pays one full re-ship + compile,
        as on one device). `last_ship` records what this call moved."""
        if self._specs is not None:
            # the step closures' in_specs were built for this delta-ness;
            # an EdgeDelta appearing/vanishing changes the arg pytree
            assert (delta is None) == (self.delta is None), (
                "set_graph cannot change whether a delta overlay exists — "
                "construct the engine with the (possibly empty) delta")
        if g.n_edges != self.n_edges:
            # an overflow rebuild changed the edge count: the consensus
            # alpha test's denominator (and, for replicated placement, the
            # view-spec pytree) are baked into the step/run closures —
            # refresh them so post-rebuild decisions use the CURRENT m
            # (they pay a retrace anyway: the view shapes moved)
            self.n_edges = g.n_edges
            if self._specs is not None:
                self._rebuild_pending = True
        self.last_ship = {"replicated_leaves_shipped": 0,
                          "replicated_leaves_total": 0,
                          "edge_shards_shipped": 0,
                          "delta_shards_shipped": 0,
                          "n_edge_shards": self.n_edge_shards}
        if self.placement == "replicated":
            self.g = self._put_rep_diff("g", g)
            self.pack = self._put_rep_diff("pack", pack)
            self.delta = (self._put_rep_diff("delta", delta)
                          if delta is not None else None)
            self._maybe_rebuild_jits()
            return
        # edge-sharded: host-side references only (live-degree counting);
        # the replicated CSR/pack never reach the mesh (CSR-free admission)
        self.g, self.pack, self.delta = g, pack, delta
        s_edges = NamedSharding(self.mesh, P(MODEL_AXIS, None))
        rep = NamedSharding(self.mesh, P())
        base_leaves = (g.out.row_ptr, g.out.col_idx, g.out.weights,
                       g.out.src_idx)
        base_changed = (self._base_leaves is None or any(
            a is not b for a, b in zip(base_leaves, self._base_leaves)))
        if base_changed:
            es, ed, ew = partition.shard_edges_np(g, self.n_edge_shards)
            self.esrc, n1 = self._place_rows("esrc", es, s_edges)
            self.edst, n2 = self._place_rows("edst", ed, s_edges)
            self.ewgt, n3 = self._place_rows("ewgt", ew, s_edges)
            self.last_ship["edge_shards_shipped"] = max(n1, n2, n3)
            self._base_leaves = base_leaves
        delta_leaves = (None if delta is None
                        else (delta.src, delta.dst, delta.w))
        delta_changed = delta is not None and (
            self._delta_leaves is None or any(
                a is not b for a, b in zip(delta_leaves, self._delta_leaves)))
        if delta is None:
            self.dsrc = self.ddst = self.dwgt = None
        elif delta_changed:
            if self.n_edge_shards == 1:
                # single shard: the round-robin layout is the identity, so
                # take partition.shard_delta's zero-copy reshape instead of
                # allocating + diffing a resliced host copy per update
                dsh = partition.shard_delta(delta, 1, self.n)
                self.dsrc = jax.device_put(dsh.src, s_edges)
                self.ddst = jax.device_put(dsh.dst, s_edges)
                self.dwgt = jax.device_put(dsh.w, s_edges)
                self.last_ship["delta_shards_shipped"] = 1
            else:
                ds, dd, dw = partition.shard_delta_np(
                    delta, self.n_edge_shards, self.n)
                self.dsrc, k1 = self._place_rows("dsrc", ds, s_edges)
                self.ddst, k2 = self._place_rows("ddst", dd, s_edges)
                self.dwgt, k3 = self._place_rows("dwgt", dw, s_edges)
                self.last_ship["delta_shards_shipped"] = max(k1, k2, k3)
            self._delta_leaves = delta_leaves
        if base_changed or self._deg_base is None:
            self._deg_base = live_degrees(g.out, None)     # O(m), per version
        if base_changed or delta_changed or self.deg is None:
            deg = self._deg_base
            if delta is not None:
                # integer adds decompose exactly: base count + O(cap) delta
                # lanes — insert-only updates never pay the O(m) recount
                deg = deg.at[delta.src].add(
                    (delta.src < self.n).astype(jnp.int32), mode="drop")
            self.deg = jax.device_put(deg, rep)
        self._maybe_rebuild_jits()

    def _maybe_rebuild_jits(self) -> None:
        """Re-close the jitted step/run over the refreshed static dims (and,
        for replicated placement, the current views' spec pytree) after an
        overflow rebuild changed the edge count."""
        if self._rebuild_pending and self._specs is not None:
            self._rebuild_pending = False
            self._build_jits()

    # -- diff shipping helpers ----------------------------------------------

    def _put_rep_diff(self, name: str, tree):
        """Broadcast `tree` to every shard, reusing the resident replica for
        every leaf that is the SAME array object as last time (the streaming
        overlay's identity-stability contract, streaming/delta.py). A
        structure change (an overflow rebuild re-buckets the ELL pack)
        re-ships everything."""
        rep = NamedSharding(self.mesh, P())
        leaves, treedef = jax.tree.flatten(tree)
        prev = self._rep_cache.get(name)
        self.last_ship["replicated_leaves_total"] += len(leaves)
        if prev is not None and prev[0] == treedef:
            _, old_leaves, old_dev = prev
            dev_leaves = []
            for nl, ol, dl in zip(leaves, old_leaves, old_dev):
                if nl is ol:
                    dev_leaves.append(dl)
                else:
                    self.last_ship["replicated_leaves_shipped"] += 1
                    dev_leaves.append(jax.device_put(nl, rep))
        else:
            self.last_ship["replicated_leaves_shipped"] += len(leaves)
            dev_leaves = [jax.device_put(l, rep) for l in leaves]
        self._rep_cache[name] = (treedef, leaves, dev_leaves)
        return jax.tree.unflatten(treedef, dev_leaves)

    def _place_rows(self, name: str, new_host: np.ndarray, sharding):
        """Place an (S, L) row-sharded view, shipping only the rows whose
        contents differ from the cached previous host slices; unchanged rows
        keep their resident per-device buffers, stitched back into the
        global view with `jax.make_array_from_single_device_arrays`.
        Returns (global array, rows shipped)."""
        prev = self._row_cache.get(name)
        s = new_host.shape[0]
        if prev is None or prev[0].shape != new_host.shape:
            dev = jax.device_put(jnp.asarray(new_host), sharding)
            shipped = s
        else:
            old_host, old_dev = prev
            changed = {r for r in range(s)
                       if not np.array_equal(new_host[r], old_host[r])}
            if not changed:
                dev, shipped = old_dev, 0
            else:
                parts = []
                for sh in old_dev.addressable_shards:
                    r = sh.index[0].start or 0
                    parts.append(
                        jax.device_put(new_host[r:r + 1], sh.device)
                        if r in changed else sh.data)
                dev = jax.make_array_from_single_device_arrays(
                    new_host.shape, old_dev.sharding, parts)
                shipped = len(changed)
        self._row_cache[name] = (new_host, dev)
        return dev, shipped

    def _views(self) -> tuple:
        if self.placement == "replicated":
            return (self.g, self.pack, self.delta)
        return (self.esrc, self.edst, self.ewgt, self.deg,
                self.dsrc, self.ddst, self.dwgt)

    # -- state construction --------------------------------------------------

    def init(self, sources, done=None) -> B.BatchState:
        """Sharded initial state for Q = len(sources) lanes (Q must divide by
        the 'data' axis). `init_batch` computes the GLOBAL consensus inputs
        before the state is scattered, so iteration 0's decision is already
        the single-device one. Edge-sharded engines init CSR-FREE: only the
        static graph dims and the cached (n,) live-degree vector enter the
        computation (DESIGN.md §11) — never the O(m) adjacency arrays."""
        sources = jnp.asarray(sources, jnp.int32)
        q = int(sources.shape[0])
        assert q % self.n_query_shards == 0, (q, self.n_query_shards)
        if self.placement == "edge_sharded":
            st = B.init_batch(self.program,
                              B.GraphDims(self.n, self.n_edges), self.cfg,
                              sources, done=done, check_caps=False,
                              deg=self.deg, telemetry=self.telemetry,
                              tele_shards=self.n_edge_shards)
        else:
            pack = self.pack if self.cfg.masked_pull else None
            st = B.init_batch(self.program, self.g, self.cfg, sources,
                              done=done, pack=pack, delta=self.delta,
                              telemetry=self.telemetry,
                              tele_shards=self.n_query_shards)
        if self._specs is None:
            self._build(st)
        return jax.device_put(st, self._shardings)

    def _build(self, st: B.BatchState) -> None:
        self._specs = state_specs(st, self.mesh)
        # an absent state field (spec None) stays None: it has no leaf to
        # place, and NamedSharding takes only a PartitionSpec
        self._shardings = jax.tree.map(
            lambda s: None if s is None else NamedSharding(self.mesh, s),
            self._specs, is_leaf=_SPEC_LEAF)
        self._build_jits()

    def _build_jits(self) -> None:
        if self.placement == "replicated":
            view_specs = (
                _replicated_specs(self.g),
                _replicated_specs(self.pack),
                _replicated_specs(self.delta) if self.delta is not None
                else None,
            )
            body = _make_replicated_step(
                self.program, self.cfg, self.n_edges, self.consensus)
        else:
            es = P(MODEL_AXIS, None)
            dspec = es if self.dsrc is not None else None
            view_specs = (es, es, es, P(), dspec, dspec, dspec)
            body = _make_edge_sharded_step(
                self.program, self.cfg, self.n, self.n_edges)
        # check_vma=False: the consensus scalars ride under a replicated
        # spec while edge-shard rows (and local consensus) carry row-local
        # values in each device's buffer — see `_normalize_scalars`
        self._step_j = jax.jit(compat.shard_map(
            body, mesh=self.mesh, in_specs=(self._specs,) + view_specs,
            out_specs=self._specs, check_vma=False))
        if self.placement == "edge_sharded":
            # the fused loop needs a 'data'-collective-free body (rows run
            # independent trip counts) — tele sums over 'model' in-loop and
            # over 'data' at exit (_normalize_scalars)
            run_body = _make_edge_sharded_step(
                self.program, self.cfg, self.n, self.n_edges,
                tele_axes=(MODEL_AXIS,))
        else:
            run_body = body
        self._run_j = jax.jit(compat.shard_map(
            self._make_run(run_body), mesh=self.mesh,
            in_specs=(self._specs,) + view_specs, out_specs=self._specs,
            check_vma=False))

    def _make_run(self, body):
        """Fused convergence loop around the per-shard step.

        Global consensus carries the psum'd live count so every shard runs
        the same trip count (required: the body contains collectives) and the
        iteration schedule matches the single-device fused loop. Local
        consensus / edge shards loop on shard-local liveness — edge-shard
        rows are bitwise-identical within a 'model' group, so their psums
        stay in lockstep without a carried global.
        """
        placement, consensus = self.placement, self.consensus

        def run(st, *views):
            if placement == "replicated" and consensus == "global":
                def cond(c):
                    return c[1] > 0

                def it(c):
                    s = body(c[0], *views)
                    return s, _live_count(s, (DATA_AXIS,))

                st, _ = jax.lax.while_loop(
                    cond, it, (st, _live_count(st, (DATA_AXIS,))))
                return st
            st = jax.lax.while_loop(
                lambda s: jnp.any(~s.done), lambda s: body(s, *views), st)
            return _normalize_scalars(st, (DATA_AXIS, MODEL_AXIS))

        return run

    # -- execution -----------------------------------------------------------

    def step(self, st: B.BatchState) -> B.BatchState:
        """One batched iteration across every shard (the scheduler's
        host-stepped path). Requires the global controller — per-shard local
        decisions would leave the carried consensus scalars shard-local."""
        assert self.consensus == "global" or self.placement == "edge_sharded"
        return self._step_j(st, *self._views())

    def run(self, st: B.BatchState):
        """Advance `st` to convergence; returns (metadata, stats)."""
        final = self._run_j(st, *self._views())
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

    @property
    def state_shardings(self):
        assert self._shardings is not None, "call init() first"
        return self._shardings


def run_sharded(program: ACCProgram, g: Graph, pack: EllPack,
                cfg: EngineConfig, mesh, sources, *,
                placement: str = "replicated", consensus: str = "global",
                delta: Optional[EdgeDelta] = None):
    """`run_batch`, sharded: Q point queries to convergence on `mesh`.
    Returns (metadata dict — field -> global (n+1, Q) —, stats)."""
    eng = ShardedBatchEngine(program, g, pack, cfg, mesh,
                             placement=placement, consensus=consensus,
                             delta=delta)
    st0 = eng.init(sources)
    return eng.run(st0)


def shard_sources(sources, n_shards: int) -> list:
    """The per-shard source slices a ('data'=n_shards) mesh assigns: shard d
    owns the contiguous block sources[d*Q/D : (d+1)*Q/D] (jax shards the
    trailing Q axis in contiguous blocks)."""
    sources = np.asarray(sources)
    q = sources.shape[0]
    assert q % n_shards == 0, (q, n_shards)
    per = q // n_shards
    return [sources[d * per:(d + 1) * per] for d in range(n_shards)]
