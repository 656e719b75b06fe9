"""Jaxpr backend: IR-level checks of the engine's collective/transfer/shape
contracts (rules ACC-J101/J102/J103 — DESIGN.md §16).

The analyzer traces every catalog program through the real engine entry
points (solo fused loop, batched fused loop, sharded replicated + edge-
sharded step/run) with abstract values — no kernels execute — and walks the
closed jaxprs.

**ACC-J101 (§9 deadlock-free barrier).** A collective inside a
`while_loop`/`cond` is only safe if every participant of its mesh axes
executes it the same number of times. We check this with a *uniformity
dataflow*: each value carries the set of mesh axes along which it may
differ across shards. Values entering a `shard_map` varying along their
sharded axes; `axis_index` introduces variation; uniforming collectives
(psum/pmin/pmax/all_gather) *remove* their axes from the set (the result
is identical on every participant); re-distributing collectives
(psum_scatter/all_to_all/ppermute) *add* theirs. A while-loop's carry is
solved to fixpoint, then the cond output's varying set is intersected with
the axes of every collective in the loop: a non-empty intersection means
one shard can leave the loop while a peer still waits at the barrier —
the §9 deadlock, caught mechanically. The two in-tree loop disciplines
pass by construction: the replicated-global loop conditions on a psum'd
live count (uniform along 'data'), and the edge-sharded fused loop keeps
its in-loop collectives on 'model' only while the cond varies along
'data' (serving/sharded.py pins this with `tele_axes=(MODEL_AXIS,)`).

**ACC-J102 (§12 transfer-free engine).** No host-callback / infeed /
outfeed / device_put primitive may be reachable from an engine jaxpr:
telemetry-off paths must not touch the host (the TRANSFER_COUNT==0 test
checks one run; this pins it in the IR for every program).

**ACC-J103 (§8 static shapes).** Each entry point must trace with abstract
values at all — a data-dependent output shape (or any trace-time failure)
surfaces here as the streaming recompile hazard it is.
"""

from __future__ import annotations

from typing import Callable, Iterable, Optional

from .findings import Finding

#: collectives whose OUTPUT is identical on every participant of their axes
#: (`psum_invariant` is psum under shard_map's varying-axes checking; the
#: `pvary` cast moves no data and is no barrier, so it passes values through)
UNIFORMING = {"psum", "psum_invariant", "pmin", "pmax", "all_gather", "psum2",
              "pmax_p", "pall"}
#: collectives whose output differs per participant (re-distributions)
VARYING = {"psum_scatter", "reduce_scatter", "all_to_all", "ppermute",
           "pshuffle", "pgather"}
COLLECTIVES = UNIFORMING | VARYING
#: primitives that touch the host or move buffers — banned in engine jaxprs
TRANSFER = {"infeed", "outfeed", "outside_call", "device_put",
            "copy_to_host_async"}

_FIXPOINT_CAP = 64      # uniformity lattice is tiny; this is unreachable


def _is_lit(atom) -> bool:
    return hasattr(atom, "val")         # Literal carries .val, Var doesn't


def _prim_axes(eqn) -> frozenset:
    """Named mesh axes a collective operates over (ints = unnamed, skipped)."""
    p = eqn.params
    ax = p.get("axes", p.get("axis_name", ()))
    if ax is None:
        ax = ()
    if not isinstance(ax, (tuple, list)):
        ax = (ax,)
    return frozenset(a for a in ax if isinstance(a, str))


def _sub_jaxprs(val) -> Iterable:
    """Every (open) jaxpr reachable from one eqn-param value."""
    if hasattr(val, "jaxpr"):                   # core.ClosedJaxpr
        yield val.jaxpr                         # (it proxies .eqns — test
    elif hasattr(val, "eqns"):                  # the wrapper FIRST)
        yield val
    elif isinstance(val, (tuple, list)):
        for x in val:
            yield from _sub_jaxprs(x)


def iter_eqns(jaxpr) -> Iterable:
    """Depth-first over every eqn in `jaxpr` and all nested sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                yield from iter_eqns(sub)


def collect_collectives(jaxpr):
    """[(primitive_name, axes)] for every collective reachable from jaxpr
    (`psum_invariant` reported as the `psum` the source wrote)."""
    out = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in COLLECTIVES:
            out.append(("psum" if name == "psum_invariant" else name,
                        _prim_axes(eqn)))
    return out


class _Analysis:
    """One uniformity-dataflow walk over one entry point's closed jaxpr."""

    def __init__(self, entry: str):
        self.entry = entry
        self.findings: list[Finding] = []

    # -- dataflow ------------------------------------------------------------

    def run(self, closed) -> None:
        jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
        self.propagate(jaxpr, [frozenset()] * len(jaxpr.invars))

    def propagate(self, jaxpr, in_sets) -> list:
        """Walk one (open) jaxpr; returns the outvars' varying-axes sets."""
        env: dict = {}
        for v in jaxpr.constvars:
            env[v] = frozenset()                # closure consts are replicated
        for v, s in zip(jaxpr.invars, in_sets):
            env[v] = s

        def read(a):
            return frozenset() if _is_lit(a) else env.get(a, frozenset())

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            joined = frozenset().union(*[read(a) for a in eqn.invars]) \
                if eqn.invars else frozenset()
            if name in UNIFORMING:
                outs = [joined - _prim_axes(eqn)] * len(eqn.outvars)
            elif name in VARYING:
                outs = [joined | _prim_axes(eqn)] * len(eqn.outvars)
            elif name == "axis_index":
                outs = [joined | _prim_axes(eqn)] * len(eqn.outvars)
            elif name == "while":
                outs = self._while(eqn, read)
            elif name == "cond":
                outs = self._cond(eqn, read)
            elif name == "scan":
                outs = self._scan(eqn, read)
            elif name == "shard_map":
                outs = self._shard_map(eqn, read)
            elif "jaxpr" in eqn.params and name != "shard_map":
                # pjit / closed_call / remat / custom_* with a single body
                inner = next(iter(_sub_jaxprs(eqn.params["jaxpr"])))
                outs = self.propagate(inner, [read(a) for a in eqn.invars])
            elif "call_jaxpr" in eqn.params:
                inner = next(iter(_sub_jaxprs(eqn.params["call_jaxpr"])))
                outs = self.propagate(inner, [read(a) for a in eqn.invars])
            else:
                outs = [joined] * len(eqn.outvars)
            for ov, s in zip(eqn.outvars, outs):
                env[ov] = s
        return [read(v) for v in jaxpr.outvars]

    # -- control flow --------------------------------------------------------

    def _while(self, eqn, read) -> list:
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        invals = [read(a) for a in eqn.invars]
        cond_consts, body_consts = invals[:cn], invals[cn:cn + bn]
        carry = list(invals[cn + bn:])
        body = p["body_jaxpr"].jaxpr
        cond = p["cond_jaxpr"].jaxpr
        for _ in range(_FIXPOINT_CAP):
            outs = self.propagate(body, body_consts + carry)
            new = [c | o for c, o in zip(carry, outs)]
            if new == carry:
                break
            carry = new
        pred, = self.propagate(cond, cond_consts + carry)
        if pred:
            self._flag_divergent_barriers("while", pred, (cond, body))
        # exit time varies along `pred`'s axes, so the results may too
        return [c | pred for c in carry]

    def _cond(self, eqn, read) -> list:
        p = eqn.params
        pred = read(eqn.invars[0])
        ops = [read(a) for a in eqn.invars[1:]]
        branches = [b for br in p["branches"] for b in _sub_jaxprs(br)]
        outs = None
        for br in branches:
            o = self.propagate(br, list(ops))
            outs = o if outs is None else [x | y for x, y in zip(outs, o)]
        if pred:
            self._flag_divergent_barriers("cond", pred, branches)
        return [o | pred for o in (outs or [])]

    def _scan(self, eqn, read) -> list:
        p = eqn.params
        nc, nk = p["num_consts"], p["num_carry"]
        invals = [read(a) for a in eqn.invars]
        consts, carry, xs = invals[:nc], list(invals[nc:nc + nk]), \
            invals[nc + nk:]
        body = next(iter(_sub_jaxprs(p["jaxpr"])))
        ys: list = []
        for _ in range(_FIXPOINT_CAP):        # static trip count: no J101 risk
            outs = self.propagate(body, consts + carry + xs)
            new = [c | o for c, o in zip(carry, outs[:nk])]
            ys = outs[nk:]
            if new == carry:
                break
            carry = new
        return carry + ys

    def _shard_map(self, eqn, read) -> list:
        p = eqn.params
        inner = next(iter(_sub_jaxprs(p["jaxpr"])))
        in_sets = []
        for a, spec in zip(eqn.invars, p["in_specs"]):
            sharded = frozenset(
                n for entry in spec if entry is not None
                for n in ((entry,) if isinstance(entry, str) else entry))
            in_sets.append(read(a) | sharded)
        self.propagate(inner, in_sets)
        # outside the shard_map we are back in global-array land: per-shard
        # variation is materialized into array dimensions, not divergence
        return [frozenset()] * len(eqn.outvars)

    # -- findings ------------------------------------------------------------

    def _flag_divergent_barriers(self, kind: str, pred_axes: frozenset,
                                 bodies) -> None:
        seen = set()
        for body in bodies:
            for name, axes in collect_collectives(body):
                bad = axes & pred_axes
                if bad and (name, tuple(sorted(bad))) not in seen:
                    seen.add((name, tuple(sorted(bad))))
                    self.findings.append(Finding(
                        "ACC-J101", self.entry, 0,
                        f"`{name}` over mesh axes {sorted(axes)} inside a "
                        f"`{kind}` whose predicate varies per shard along "
                        f"{sorted(pred_axes)} — a shard can exit while a "
                        f"peer waits at the barrier (deadlock, DESIGN.md "
                        f"§9)"))


def check_closed_jaxpr(entry: str, closed) -> list[Finding]:
    """Run ACC-J101 + ACC-J102 over one already-traced closed jaxpr."""
    an = _Analysis(entry)
    an.run(closed)
    findings = an.findings
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    flagged = set()
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if ("callback" in name or name in TRANSFER) and name not in flagged:
            flagged.add(name)
            findings.append(Finding(
                "ACC-J102", entry, 0,
                f"host-transfer primitive `{name}` reachable from this "
                f"engine entry point — telemetry-off paths must be "
                f"transfer-free (DESIGN.md §12)"))
    return findings


def check_entry(entry: str, thunk: Callable[[], object]) -> list[Finding]:
    """Trace one entry point (thunk returns its closed jaxpr) and check it.
    Trace-time failures — including data-dependent output shapes — become
    ACC-J103 findings instead of crashing the lint run."""
    try:
        closed = thunk()
    except Exception as e:                              # noqa: BLE001
        msg = f"{type(e).__name__}: {e}"
        return [Finding("ACC-J103", entry, 0,
                        "entry point failed abstract tracing (static-shape "
                        f"discipline, DESIGN.md §8): {msg[:300]}")]
    return check_closed_jaxpr(entry, closed)


# ---------------------------------------------------------------------------
# engine entry points
# ---------------------------------------------------------------------------


def catalog_entries(programs: Optional[dict] = None, scale: int = 6,
                    sharded: bool = True):
    """Yield (entry_name, thunk) for every catalog program x engine path.

    Everything here builds tiny concrete inputs (a scale-`scale` RMAT) and
    traces the REAL jitted entry points with `jax.make_jaxpr` — graph and
    pack ride along as closure constants, only the engine state is
    abstract, so no fixpoint ever executes. Mesh extents adapt to the
    visible device count ((2,1)/(1,2) under a forced host mesh, (1,1)
    under plain pytest) — the axis *semantics* the §9 rule checks are
    extent-independent: psum over a size-1 axis still appears in the IR.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import engine as E
    from repro.graph import generators, pack_ell
    from repro.launch.catalog import make_catalog
    from repro.serving import batch_engine as B
    from repro.serving.scheduler import default_config
    from repro.serving.sharded import ShardedBatchEngine, make_serving_mesh

    if programs is None:
        programs = make_catalog()

    g = generators.rmat(scale, 4, seed=1, directed=True)
    pack = pack_ell(g.inc)
    cfg = default_config(g, max_iters=64)
    nd = jax.device_count()
    q = 2

    for name, program in programs.items():
        kw = {"source": jnp.int32(0)} if B._accepts_source(program) else {}

        def solo(program=program, kw=kw):
            st0 = E.init_state(program, g, cfg, **kw)
            return jax.make_jaxpr(
                lambda st: E._run_fused_all(program, g, pack, cfg, st,
                                            None, None))(st0)

        yield f"jaxpr:{name}/solo_fused", solo

        def batched(program=program):
            st0 = B.init_batch(program, g, cfg, list(range(q)))
            return jax.make_jaxpr(
                lambda st: B._run_fused(program, g, pack, cfg, st,
                                        None))(st0)

        yield f"jaxpr:{name}/batched_fused", batched

        if not sharded:
            continue

        def _sharded(placement, telemetry, which, program=program):
            if placement == "replicated":
                mesh = make_serving_mesh(min(2, nd), 1)
            else:
                mesh = make_serving_mesh(1, min(2, nd))
            eng = ShardedBatchEngine(program, g, pack, cfg, mesh,
                                     placement=placement,
                                     telemetry=telemetry)
            st0 = eng.init(list(range(q)))
            views = eng._views()
            fn = eng._run_j if which == "run" else eng._step_j
            return jax.make_jaxpr(lambda st: fn(st, *views))(st0)

        for placement in ("replicated", "edge_sharded"):
            for telemetry in ((False, True) if placement == "edge_sharded"
                              else (False,)):
                tag = "_tele" if telemetry else ""
                for which in ("run", "step"):

                    def entry(placement=placement, telemetry=telemetry,
                              which=which):
                        return _sharded(placement, telemetry, which)

                    yield (f"jaxpr:{name}/sharded_{placement}{tag}_{which}",
                           entry)


def check_catalog(programs: Optional[dict] = None, scale: int = 6,
                  sharded: bool = True):
    """Run the jaxpr backend over every catalog entry point.
    Returns (findings, n_entries_checked)."""
    findings: list[Finding] = []
    n = 0
    for entry, thunk in catalog_entries(programs, scale, sharded):
        findings.extend(check_entry(entry, thunk))
        n += 1
    return findings, n
