"""The general traffic generator: one function reads every traffic mix.

A mix is a JSON file under `bench/traffic/`, found by its name. Keys:

  loop          "closed": `outstanding` requests per program, each client
                sending its next request when it has its answer;
                "open": arrivals on a clock, whatever the server does.
  programs      {program: weight}; a closed loop runs `outstanding`
                clients for each program, an open loop draws each arrival's
                program by weight.
  sources       {"draw": "distinct"}: vertices of nonzero degree in a
                seeded random order, never repeated (Graph500's search-key
                rule); {"draw": "zipf", "exponent": s}: rank i drawn with
                weight 1 / i**s over the same vertices, ranked in a seeded
                random order, so popularity does not follow degree.
  arrival       open loop: "poisson" or "mmpp" (burst_factor, burst_frac,
                burst_dwell_s), at `rate_qps`. A poisson mix sends
                round(rate_qps * seconds) arrivals at sorted uniform times:
                the Poisson process given its count, so every seed offers
                the same load in another order.
  check_per_program   answers of each program compared with the reference.

Everything is drawn from one seeded numpy Generator. A run's plan
(`for_graph`) is drawn from the configuration's `structure_seed` in the
structure's own labels and mapped to the run's: every `--seed` sends the
same queries at the same times, each from the same vertex of the same
weighted graph, so the seed changes the labels and not the work.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple

import numpy as np

#: warm-up sources, set apart before the traffic's own are drawn
WARM = "warm"


class Arrival(NamedTuple):
    t: float        # seconds after the window opens
    program: str
    source: int


def candidates(deg: np.ndarray) -> np.ndarray:
    """Vertices with at least one edge: the only valid search keys."""
    return np.nonzero(deg)[0]


def source_draws(mix: dict, rng: np.random.Generator, cand: np.ndarray,
                 warm: int, count: int):
    """(warm-up sources, traffic sources): `warm` distinct vertices kept
    for warm-up, then `count` traffic sources by the mix's rule."""
    order = rng.permutation(cand)
    spec = mix["sources"]
    if spec["draw"] == "distinct":
        if warm + count > order.size:
            raise ValueError(f"{warm + count} distinct sources asked of "
                             f"{order.size} vertices")
        return order[:warm], order[warm:warm + count]
    if spec["draw"] == "zipf":
        p = 1.0 / np.arange(1, order.size + 1) ** float(spec["exponent"])
        picks = rng.choice(order.size, size=count, p=p / p.sum())
        return order[:warm], order[picks]
    raise ValueError(f"unknown source draw {spec['draw']!r}")


def _poisson_times(rate: float, seconds: float, rng) -> np.ndarray:
    return np.sort(rng.uniform(0.0, seconds, int(round(rate * seconds))))


def _mmpp_times(mix: dict, seconds: float, rng) -> np.ndarray:
    """Two-state Markov-modulated Poisson arrivals with mean rate
    `rate_qps`: a `burst_frac` share of the time at `burst_factor` times
    the rate (as `repro.slo.workload`, whose arithmetic this copies)."""
    rate = float(mix["rate_qps"])
    f = min(max(float(mix["burst_frac"]), 1e-6), 1.0 - 1e-6)
    hi = rate * float(mix["burst_factor"])
    lo = max(rate * (1.0 - f * float(mix["burst_factor"])) / (1.0 - f), 0.0)
    dwell_hi = float(mix["burst_dwell_s"])
    dwell_lo = dwell_hi * (1.0 - f) / f
    t, out, high = 0.0, [], False
    seg_end = rng.exponential(dwell_lo)
    while t < seconds:
        r = hi if high else lo
        nxt = t + rng.exponential(1.0 / r) if r > 0 else seg_end
        if nxt >= seg_end:
            t = seg_end
            high = not high
            seg_end = t + rng.exponential(dwell_hi if high else dwell_lo)
        else:
            t = nxt
            if t < seconds:
                out.append(t)
    return np.asarray(out)


def open_arrivals(mix: dict, rng, seconds: float,
                  sources: np.ndarray) -> List[Arrival]:
    kind = mix["arrival"]
    if kind == "poisson":
        times = _poisson_times(float(mix["rate_qps"]), seconds, rng)
    elif kind == "mmpp":
        times = _mmpp_times(mix, seconds, rng)
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    names = list(mix["programs"])
    w = np.asarray([mix["programs"][p] for p in names], np.float64)
    picks = rng.choice(len(names), size=times.size, p=w / w.sum())
    if times.size > sources.size:
        raise ValueError("more arrivals than drawn sources")
    return [Arrival(float(t), names[k], int(s))
            for t, k, s in zip(times, picks, sources)]


def plan(mix: dict, seed: int, deg: np.ndarray, lanes: int,
         seconds: float, labels=None) -> Dict[str, object]:
    """Everything a run sends, from the seed: warm-up sources per program,
    and either the open loop's arrivals or the closed loop's source queue
    per program. `labels`, if given, maps each drawn vertex to the label
    the run sends."""
    rng = np.random.default_rng(seed)
    progs = list(mix["programs"])
    cand = candidates(deg)
    warm_n = lanes * len(progs)
    if mix["loop"] == "open":
        peak = float(mix["rate_qps"]) * float(mix.get("burst_factor", 1.0))
        count = int(peak * seconds * 2) + 16
    else:
        # far more than any client reaches in the window
        count = min(cand.size - warm_n, 20000 * len(progs))
    warm, srcs = source_draws(mix, rng, cand, warm_n, count)
    if labels is not None:
        warm, srcs = labels[warm], labels[srcs]
    out: Dict[str, object] = {
        WARM: {p: [int(s) for s in warm[i * lanes:(i + 1) * lanes]]
               for i, p in enumerate(progs)}}
    if mix["loop"] == "open":
        out["arrivals"] = open_arrivals(mix, rng, seconds, srcs)
    else:
        out["queues"] = {p: [int(s) for s in srcs[i::len(progs)]]
                         for i, p in enumerate(progs)}
    return out


def for_graph(mix: dict, edges, structure_seed: int, lanes: int,
              seconds: float) -> Dict[str, object]:
    """The plan of a run on `edges` (`bench.gen.graph500.Edges`)."""
    return plan(mix, structure_seed, edges.degrees()[edges.perm], lanes,
                seconds, labels=edges.perm)
