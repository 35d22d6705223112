"""Distributed coarsening: sharded clustering + contraction must reproduce
the host path bit-for-bit (integer-weight graphs), conserve weights, and make
the on-device V-cycle P-invariant (same cut at P=1 and P=8 from one seed)."""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(P)d"
import json
import numpy as np, jax, jax.numpy as jnp
from repro.graphs import grid2d, rmat
from repro.core import coarsen as C
from repro.core.partition import edge_cut
from repro.distributed import dpartition
from repro.distributed.dgraph import shard_graph, labels_from_sharded, sharded_to_graph
from repro.distributed.dmultilevel import make_pe_mesh
from repro.distributed.dcoarsen import dcoarsen_hierarchy

P = %(P)d
mesh, _ = make_pe_mesh(P)
out = {}
for name, g, k in (("grid", grid2d(40, 40), 4),
                   ("rmat", rmat(scale=11, edge_factor=6, seed=2), 8)):
    rec = {}
    key = jax.random.PRNGKey(5)

    # hierarchy equivalence vs the host coarsener (same key)
    levels_h, coarsest_h = C.coarsen_hierarchy(g, k, key)
    sg0 = shard_graph(g, P)
    levels_s, coarsest_s = dcoarsen_hierarchy(mesh, sg0, k, key)
    rec["levels_equal"] = len(levels_h) == len(levels_s)
    rec["n_levels"] = len(levels_s)

    maps_equal, graphs_equal, conserve = True, True, True
    for (gf, map_h), (fine_sg, map_sh, coarse_sg) in zip(levels_h, levels_s):
        map_s = np.asarray(labels_from_sharded(fine_sg, map_sh))
        maps_equal &= bool(np.array_equal(map_s, np.asarray(map_h)))
        gc = sharded_to_graph(coarse_sg)
        ch, _ = C.contract(gf, map_h)  # identical coarse graph re-derived
        graphs_equal &= gc.n == ch.n
        graphs_equal &= bool(np.array_equal(np.asarray(gc.col), np.asarray(ch.col)))
        graphs_equal &= bool(np.array_equal(np.asarray(gc.ew), np.asarray(ch.ew)))
        graphs_equal &= bool(np.array_equal(np.asarray(gc.nw), np.asarray(ch.nw)))
        # conservation: node weight exactly; edge weight = inter-cluster
        # weight of the fine level (directed total = 2 x cut of the mapping)
        conserve &= float(gc.total_node_weight) == float(gf.total_node_weight)
        conserve &= float(gc.total_edge_weight) == 2.0 * float(
            edge_cut(gf, jnp.asarray(map_h)))
    rec["maps_equal"] = maps_equal
    rec["graphs_equal"] = graphs_equal
    rec["conserve"] = conserve
    gcs = sharded_to_graph(coarsest_s)
    rec["coarsest_equal"] = (
        gcs.n == coarsest_h.n
        and bool(np.array_equal(np.asarray(gcs.col), np.asarray(coarsest_h.col)))
        and bool(np.array_equal(np.asarray(gcs.ew), np.asarray(coarsest_h.ew)))
        and bool(np.array_equal(np.asarray(gcs.nw), np.asarray(coarsest_h.nw)))
    )

    # full V-cycle: sharded coarsening == host-coarsening fallback, bit-wise
    rs = dpartition(g, k=k, P=P, seed=0, refiner="d4xjet", max_inner=10,
                    coarsen="sharded")
    rh = dpartition(g, k=k, P=P, seed=0, refiner="d4xjet", max_inner=10,
                    coarsen="host")
    rec["vcycle_labels_equal"] = bool(
        np.array_equal(np.asarray(rs.labels), np.asarray(rh.labels)))
    rec["cut"] = rs.cut
    rec["imb"] = rs.imbalance
    out[name] = rec
print("RESULT::" + json.dumps(out))
"""


def _run(P):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", SCRIPT % {"P": P}], env=env,
                          capture_output=True, text=True, timeout=2400)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT::"):
            return json.loads(line[len("RESULT::"):])
    raise AssertionError(f"no RESULT line in output: {proc.stdout[-2000:]}")


@pytest.fixture(scope="module")
def p8():
    return _run(8)


@pytest.fixture(scope="module")
def p1():
    return _run(1)


def test_sharded_hierarchy_matches_host(p8):
    for name, rec in p8.items():
        assert rec["levels_equal"], (name, rec)
        assert rec["n_levels"] >= 1, (name, rec)
        assert rec["maps_equal"], (name, rec)
        assert rec["graphs_equal"], (name, rec)
        assert rec["coarsest_equal"], (name, rec)


def test_contraction_conserves_weights(p8):
    for name, rec in p8.items():
        assert rec["conserve"], (name, rec)


def test_vcycle_sharded_equals_host_fallback(p8):
    for name, rec in p8.items():
        assert rec["vcycle_labels_equal"], (name, rec)
        assert rec["imb"] <= 0.031, (name, rec)


def test_vcycle_p_invariant(p8, p1):
    # a distributed run and a single-device run from the same seed report
    # the same cut (tentpole acceptance; djet.py's determinism contract)
    for name in p8:
        assert p8[name]["cut"] == p1[name]["cut"], (name, p8[name], p1[name])
        assert p8[name]["vcycle_labels_equal"] and p1[name]["vcycle_labels_equal"]


def test_cluster_round_compiles_once_per_level():
    # round 1's leader ids are placed on the mesh like every later round's
    # output, so a level's clustering rounds share one compiled program
    # (an unplaced first input compiled the round twice per level — on a
    # TPU host that is 15–35 s per level)
    import jax

    from repro.distributed import dcoarsen
    from repro.distributed.dgraph import shard_graph
    from repro.distributed.dmultilevel import make_pe_mesh
    from repro.graphs import grid2d

    compiled = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    mesh, P = make_pe_mesh(1)
    # a shape no other test clusters, so the round is compiled here
    sg = shard_graph(grid2d(17, 19), P, mesh=mesh)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        dcoarsen.dcluster(mesh, sg, 64.0, jax.random.PRNGKey(0), rounds=3)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiled.count("jit(cluster_round)") == 1, compiled
