"""Host spans, device scopes and per-call counters (``repro.telemetry``).

A ``dpartition`` call counts every device-to-host scalar read by site and
every dispatch of a named program; the counts follow from its code paths:
per coarsening attempt one ``cap`` read, one ``moved`` read per cluster
round and (sharded coarsening) one ``nc`` and one ``mmax``; per refined
level one degree probe (the Pallas gain sizes its table from it) and one
refinement dispatch.  The profiler changes none of it, and the level
program carries the five rung scopes in its op metadata."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core.partition import l_max
from repro.core.refine import temperature_schedule
from repro.distributed import dpartition
from repro.graphs import grid2d
from repro.refine import drivers

KW = dict(k=4, P=1, seed=0, refiner="djet", max_inner=8, gain="pallas")


@pytest.fixture(scope="module")
def graph():
    return grid2d(32, 32)


def _run(g, **kw):
    d0, t0 = drivers.DISPATCH_COUNT, drivers.TRACE_COUNT
    r = dpartition(g, **{**KW, **kw})
    return r, drivers.DISPATCH_COUNT - d0, drivers.TRACE_COUNT - t0


@pytest.mark.parametrize("coarsen", ["sharded", "host"])
def test_counters_follow_the_code_paths(graph, coarsen):
    r, dispatched, _ = _run(graph, coarsen=coarsen)
    hr, d = r.counters["host_reads"], r.counters["dispatches"]
    # a coarsening attempt per coarse level, and one more where the last
    # attempt shrank too little
    attempts = hr["cap"]
    assert r.levels - 1 <= attempts <= r.levels
    assert attempts <= hr["moved"] == d["cluster_round"] <= 5 * attempts
    assert hr["total_w"] == 1
    if coarsen == "sharded":
        assert hr["nc"] == hr["mmax"] == attempts
        assert d["count"] == d["contract"] == d["compress"] == attempts
        assert d["uncoarsen"] == r.levels - 1
    else:  # contraction and projection on the host: no program, no read
        assert not {"nc", "mmax"} & set(hr)
        assert not {"count", "contract", "compress", "uncoarsen"} & set(d)
    # one refinement dispatch and one degree probe per level
    assert d["refine_sharded"] == hr["max_deg"] == d["max_deg"] == r.levels
    # four initial-partitioning restarts, each refined once and read twice
    assert d["refine_single"] == hr["init_cut"] == hr["init_ov"] == 4
    assert hr["cut"] == hr["imbalance"] == 1
    expected = {"total_w", "cap", "moved", "max_deg", "init_cut", "init_ov",
                "cut", "imbalance"}
    assert set(hr) == expected | ({"nc", "mmax"} if coarsen == "sharded"
                                  else set())
    assert dispatched == d["refine_sharded"] + d["refine_single"]


def test_the_profiler_changes_no_label_or_count(graph, tmp_path):
    _run(graph)  # compiles whatever is not compiled yet
    off = _run(graph)
    with jax.profiler.trace(str(tmp_path)):
        on = _run(graph)
    assert np.array_equal(np.asarray(off[0].labels), np.asarray(on[0].labels))
    assert off[0].counters == on[0].counters
    assert off[1:] == on[1:]
    assert off[2] == 0


def test_counts_stay_with_their_own_context():
    def other():
        telemetry.count_dispatch("elsewhere")
        telemetry.host_read("elsewhere", jnp.int32(3))

    with telemetry.record() as outer:
        with telemetry.record() as inner:
            assert telemetry.host_read("x", jnp.float32(2.5), float) == 2.5
            t = threading.Thread(target=other)
            t.start()
            t.join()
        telemetry.count_dispatch("p")
    assert inner.as_dict() == {"host_reads": {"x": 1}, "dispatches": {}}
    assert outer.as_dict() == {"host_reads": {"x": 1},
                               "dispatches": {"p": 1}}


def _lower_single(g, labels, variant):
    return drivers._refine_single_jit.lower(
        g, labels, jax.random.PRNGKey(0), l_max(g, 4, 0.03),
        jnp.asarray(temperature_schedule(2), jnp.float32), k=4, patience=2,
        max_inner=4, gain_kind="jnp", max_deg=None, interpret=None,
        variant=variant)


def _lower_sharded(g, labels, variant):
    """The benchmark's level program: all-gather BSP, Pallas gain."""
    from repro.distributed.dgraph import (labels_to_sharded, owned_mask,
                                          shard_graph)
    from repro.distributed.dmultilevel import make_pe_mesh

    mesh, _ = make_pe_mesh(1)
    sg = shard_graph(g, 1, mesh=mesh)
    _, fn = drivers._sharded_level_fn(mesh, 4, sg.n_local, sg.n_real, 2, 4,
                                      "pallas", 4, None, variant)
    return fn.lower(sg.src, sg.dst, sg.ew, sg.nw, owned_mask(sg),
                    sg.vtx_start, labels_to_sharded(sg, labels),
                    jax.random.PRNGKey(0), jnp.float32(l_max(g, 4, 0.03)),
                    jnp.asarray(temperature_schedule(2), jnp.float32))


@pytest.mark.parametrize("lower,variant", [
    (_lower_single, "jet"), (_lower_single, "jetlp"),
    (_lower_single, "jet_h"), (_lower_single, "jet_v"),
    (_lower_sharded, "jet")],
    ids=["single-jet", "single-jetlp", "single-jet_h", "single-jet_v",
         "sharded-pallas-jet"])
def test_the_level_program_carries_the_rung_scopes(lower, variant):
    g = grid2d(8, 8)
    labels = jnp.arange(g.n, dtype=jnp.int32) % 4
    text = lower(g, labels, variant).as_text(debug_info=True)
    for scope in ("gain", "move", "afterburner", "rebalance", "cut"):
        assert f"/{scope}/" in text, scope
