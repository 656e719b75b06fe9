"""The benchmark's Graph500 generator (bench/gen/graph500.py), on the CPU."""

import sys
from pathlib import Path

import numpy as np

import jax

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.gen import graph500  # noqa: E402

INIT = (0.57, 0.19, 0.19, 0.05)


def test_quadrant_frequencies_follow_the_initiator():
    # unpermuted, the top bit pair of each edge picks its quadrant
    scale = 12
    src, dst = graph500.kronecker(jax.random.key(5), scale, 16, INIT)
    top_s = np.asarray(src) >> (scale - 1)
    top_d = np.asarray(dst) >> (scale - 1)
    got = [np.mean((top_s == i) & (top_d == j)) for i in (0, 1)
           for j in (0, 1)]
    # 65,536 edges: a share's standard error is under 0.002
    np.testing.assert_allclose(got, INIT, atol=0.01)


def test_a_seed_gives_the_same_edges_and_weights():
    a = graph500.generate(2**31 + 77, 10, 16, INIT, 20)
    b = graph500.generate(2**31 + 77, 10, 16, INIT, 20)
    c = graph500.generate(2**31 + 78, 10, 16, INIT, 20)
    for x, y in zip(a[:3], b[:3]):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.dst, c.dst)
    # the structure is the configuration's: the same weighted graph under
    # the other seed's labels
    np.testing.assert_array_equal(np.sort(a.degrees()), np.sort(c.degrees()))
    to_a = a.perm[np.argsort(c.perm)]      # c's label -> a's label
    key = to_a[c.src].astype(np.int64) * a.n + to_a[c.dst]
    order = np.argsort(key)
    np.testing.assert_array_equal(key[order],
                                  a.src.astype(np.int64) * a.n + a.dst)
    np.testing.assert_array_equal(c.w[order], a.w)
    d = graph500.generate(2**31 + 77, 10, 16, INIT, 21)
    assert not np.array_equal(np.sort(a.degrees()), np.sort(d.degrees()))


def test_weights_lie_in_zero_one_and_the_list_is_symmetric_without_loops():
    e = graph500.generate(3, 10, 16, INIT, 20)
    assert e.w.min() > 0 and e.w.max() <= 1
    assert np.all(e.src != e.dst)
    key = e.src.astype(np.int64) * e.n + e.dst
    assert np.all(np.diff(key) > 0)          # sorted, no duplicate pair
    fwd = dict(zip(key.tolist(), e.w.tolist()))
    back = e.dst.astype(np.int64) * e.n + e.src
    assert [fwd[k] for k in back.tolist()] == e.w.tolist()


def test_one_direction_rebuilds_the_same_graph_in_the_program():
    from repro.graph import csr

    e = graph500.generate(9, 9, 16, INIT, 20)
    s, d, w = e.one_direction()
    g = csr.from_edges(s, d, e.n, w, directed=False)
    np.testing.assert_array_equal(np.asarray(g.out.src_idx), e.src)
    np.testing.assert_array_equal(np.asarray(g.out.col_idx), e.dst)
    np.testing.assert_array_equal(np.asarray(g.out.weights), e.w)
