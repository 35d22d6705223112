"""The frozen generator copy reproduces the recorded counts of its
instances (directed slots, maximum degree, isolated vertices)."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import graphs  # noqa: E402


def counts(spec, seed):
    n, u, v = graphs.generate(spec, seed)
    a, b, w = graphs.edge_list(u, v)
    deg = np.bincount(np.concatenate([a, b]), minlength=n)
    return 2 * len(a), int(deg.max()), int((deg == 0).sum())


@pytest.mark.parametrize("spec,seed,expect", [
    ({"generator": "rgg2d", "n": 32768, "avg_deg": 8.0}, 0, (260986, 21)),
    ({"generator": "rgg2d", "n": 32768, "avg_deg": 8.0}, 1, (261528, 25)),
    ({"generator": "rgg2d", "n": 131072, "avg_deg": 8.0}, 0, (1044916, 21)),
    ({"generator": "rmat", "scale": 14, "edge_factor": 16, "a": 0.57,
      "b": 0.19, "c": 0.19}, 0, (425218, 3582, 3848)),
])
def test_recorded_counts(spec, seed, expect):
    assert counts(spec, seed)[:len(expect)] == expect


def test_program_graph_matches_edge_list():
    """The program's from_coo and the check's edge list see one graph."""
    from repro.core.graph import from_coo

    n, u, v = graphs.generate({"generator": "rmat", "scale": 9,
                               "edge_factor": 8, "a": 0.57, "b": 0.19,
                               "c": 0.19}, 5)
    a, b, w = graphs.edge_list(u, v)
    g = from_coo(n, u, v)
    src, col, ew = (np.asarray(x) for x in (g.src, g.col, g.ew))
    up = src < col
    assert np.array_equal(src[up], a) and np.array_equal(col[up], b)
    assert np.array_equal(ew[up].astype(np.int64), w)


def test_every_seed_gets_the_same_graph_in_another_order():
    """The one-shot driver's window graph comes from generator seed 0;
    --seed only orders the edge list, which the program's from_coo puts in
    canonical order: every seed hands the program the same graph and the
    check the same edge list.  Another generator seed draws another
    graph."""
    from bench import harness

    oneshot = harness.load_driver({"driver": "oneshot"})
    config = {"graph": {"generator": "rgg2d", "n": 2048, "avg_deg": 8.0}}
    g1, e1 = oneshot.load_graph(config, 0, 2**31 + 1)
    g2, e2 = oneshot.load_graph(config, 0, 2**31 + 2)
    for x, y in zip(e1[1:], e2[1:]):
        assert np.array_equal(x, y)
    for name in ("row_ptr", "col", "src", "ew", "nw"):
        assert np.array_equal(np.asarray(getattr(g1, name)),
                              np.asarray(getattr(g2, name)))
    _, e3 = oneshot.load_graph(config, 2**31 + 5, 2**31 + 5)
    assert len(e3[1]) != len(e1[1])
    assert oneshot.load_graph(config, -1, -1)[1][0] == 2048
