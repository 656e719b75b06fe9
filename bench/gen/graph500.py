"""Graph500 Kronecker graph, generated on the device from a seed.

Follows the Graph500 specification (v3, section 3: "Graph Generation")
kernel-0 generator: `edge_factor * 2**scale` edges, each built bit by bit
from the initiator (A, B, C, D), then vertex labels permuted; the benchmark
treats the list as undirected, drops self-loops and keeps one edge per
vertex pair (the lightest, as the program's own `from_edges` does).

Two seeds: the configuration's `structure_seed` draws the Kronecker bits
and the weights, and the run's `--seed` draws the vertex permutation. So
every run's graph is the same weighted graph under other labels: the same
degree multiset, which fixes the shapes the program compiles for (its ELL
buckets count vertices by degree), and the same work for a query from the
same vertex of the structure (`Edges.perm` maps it to the run's label).

Weights are uniform in (0, 1]: the specification's [0, 1) with 0 left out,
because a stored 0 is a missing edge in a scipy sparse reference.

`generate` returns the symmetric, deduplicated edge list as host arrays
sorted by (source, destination): the list the references read. Everything
up to the host transfer runs in one jitted call per shape.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp


class Edges(NamedTuple):
    """A symmetric edge list sorted by (src, dst), one entry per direction."""

    src: np.ndarray    # int32 (m,)
    dst: np.ndarray    # int32 (m,)
    w: np.ndarray      # float32 (m,), in (0, 1]
    n: int
    perm: np.ndarray   # int32 (n,): structure label -> this run's label

    def degrees(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n)

    def one_direction(self):
        """(src, dst, w) with src < dst: what `from_edges(directed=False)`
        symmetrises back into this list."""
        keep = self.src < self.dst
        return self.src[keep], self.dst[keep], self.w[keep]


@partial(jax.jit, static_argnums=(1, 2, 3))
def kronecker(key, scale: int, edge_factor: int,
              initiator: Sequence[float]):
    """(src, dst) int32 device arrays of the raw, unlabelled edge list:
    duplicates and self-loops kept, as the specification's generator makes
    them."""
    a, b, c, _d = initiator
    m = edge_factor << scale
    ab = a + b
    a_norm = a / ab
    c_norm = c / (1.0 - ab)

    def level(i, carry):
        src, dst = carry
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        ii = jax.random.uniform(k1, (m,)) > ab
        jj = jax.random.uniform(k2, (m,)) > jnp.where(ii, c_norm, a_norm)
        return (src * 2 + ii.astype(jnp.int32),
                dst * 2 + jj.astype(jnp.int32))

    zero = jnp.zeros((m,), jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zero, zero))


@jax.jit
def weights(key, src):
    """One weight in (0, 1] per raw edge, from `key`."""
    return 1.0 - jax.random.uniform(key, src.shape, jnp.float32)


@partial(jax.jit, static_argnums=(3,))
def relabel(key, src, dst, n: int):
    """The vertex permutation drawn from `key`, and the edges under it."""
    perm = jax.random.permutation(key, n).astype(jnp.int32)
    return perm[src], perm[dst], perm


@partial(jax.jit, static_argnums=(3,))
def symmetric_dedup(src, dst, w, n: int):
    """Both directions of every edge, self-loops dropped, sorted by (src,
    dst, w); `keep` marks the lightest entry of each (src, dst) pair."""
    u = jnp.concatenate([src, dst])
    v = jnp.concatenate([dst, src])
    ww = jnp.concatenate([w, w])
    u = jnp.where(u == v, n, u)            # self-loops sort last, dropped
    u, v, ww = jax.lax.sort((u, v, ww), num_keys=3)
    first = jnp.concatenate([
        jnp.ones((1,), bool), (u[1:] != u[:-1]) | (v[1:] != v[:-1])])
    return u, v, ww, first & (u < n)


def _key(seed: int):
    """A key from any whole number up to 64 bits."""
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def generate(seed: int, scale: int, edge_factor: int,
             initiator: Sequence[float], structure_seed: int) -> Edges:
    """The benchmark's graph for `seed`: same seed, same edges and weights."""
    n = 1 << scale
    k_bits, k_w = jax.random.split(_key(structure_seed))
    src, dst = kronecker(k_bits, scale, edge_factor, tuple(initiator))
    w = weights(k_w, src)
    u, v, perm = relabel(_key(seed), src, dst, n)
    u, v, ww, keep, perm = jax.device_get(
        symmetric_dedup(u, v, w, n) + (perm,))
    return Edges(u[keep], v[keep], ww[keep], n, perm)


def for_config(cfg: dict, seed: int) -> Edges:
    """The graph a configuration file describes, for `seed`."""
    g = cfg["graph"]
    return generate(seed, int(cfg["scale"]), int(cfg["edge_factor"]),
                    g["initiator"], int(g["structure_seed"]))
