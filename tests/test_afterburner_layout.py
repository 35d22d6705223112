"""The afterburner in the gain kernel's (D, N) layout (``comm.DenseView``)
against the m-slot layout (``comm.EdgeView``).

The two layouts sum the same integer weights in another order, so every
delta and move must agree bit for bit (DESIGN §1) — for both virtual
orders, every jet-mode variant, the three comm backends at P = 1 and P = 8,
and the vmapped batched path.  The graphs hold ragged rows, a hub whose
degree is exactly the table's height D, isolated vertices, padded slots
(the sharded and batched layouts pad both vertices and edges) and edge
weights up to 2^20, with every sum below 2^24.

Also here: ``level_backends`` names the layout each level ran, and the
afterburner round that ``test_schedule_property`` fuzzes agrees across the
layouts on the example that falsifies its no-cut-increase check.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.graph import from_coo
from repro.distributed.dgraph import labels_to_sharded, owned_mask, shard_graph
from repro.distributed.dmultilevel import make_pe_mesh
from repro.distributed.halo import halo_labels_to_sharded, shard_graph_halo
from repro.graphs.batch import from_graphs
from repro.refine import engine
from repro.refine.comm import (AllGatherComm, DenseView, EdgeView, HaloComm,
                               SingleComm, edge_view_from_graph,
                               halo_edge_view)
from repro.refine.drivers import _batched_edge_view, _sharded_edge_view
from repro.refine.gain import JnpGain, PallasGain
from repro.refine.variants import resolve_variant
from repro.sharding.compat import shard_map

HUB = 16          # the hub's degree: the table height D at deg_chunk 8
VARIANTS = ("jet", "jetlp", "jet_h", "jet_v")


def make_graph(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(40, 100))
    core = n - 3                                  # 3 isolated vertices
    hub_nb = rng.choice(np.arange(1, core), HUB, replace=False)
    hub_w = rng.integers(1, 5, HUB).astype(np.float32)
    hub_w[:4] = [2**20, 2**20 - 1, 2**19 + 3, 2**20]
    m = int(1.5 * core)
    u = rng.integers(1, core, m)
    v = rng.integers(1, core, m)
    w = rng.integers(1, 9, m).astype(np.float32)
    g = from_coo(n, np.concatenate([np.zeros(HUB, np.int64), u]),
                 np.concatenate([hub_nb, v]), np.concatenate([hub_w, w]),
                 nw=rng.integers(1, 4, n).astype(np.float32))
    deg = np.diff(np.asarray(g.row_ptr))
    assert deg[0] == HUB == deg.max() and (deg[-3:] == 0).all(), deg
    assert 2 * float(np.asarray(g.ew).sum()) < 2**24
    k = int(rng.integers(2, 6))
    labels = rng.integers(0, k, n).astype(np.int32)
    locked = rng.random(n) < 0.2
    tau = float(rng.choice([0.0, 0.5, 1.0]))
    return g, k, labels, locked, tau


def cell(cm, ev, labels, locked, tau, k):
    # the same gains feed both layouts' deltas, so a difference can only
    # come from the layout; the moves run each variant end to end beside
    # each gain backend
    slot = JnpGain(k)
    dense = PallasGain(ev, k, HUB, tile_n=128, deg_chunk=8, interpret=True)
    assert type(slot.layout(ev)) is EdgeView
    assert type(dense.layout(ev)) is DenseView
    assert dense.layout(ev).eslot.shape[0] == HUB
    lv_e = engine._head_labels(cm, ev, labels)
    own, gain, target = slot.best(ev, lv_e, labels, None)
    cand = engine.candidate_set(ev, labels, own, gain, target, tau, locked)
    out = {"cand": cand}
    for name, gb in (("slot", slot), ("dense", dense)):
        lay = gb.layout(ev)
        for order in ("gain", "vertex"):
            out[f"delta/{order}/{name}"] = engine.afterburner_delta(
                cm, lay, labels, lv_e, gain, target, cand, order)
        for v in VARIANTS:
            new, moved = resolve_variant(v).move(cm, gb, ev, labels, locked,
                                                 jnp.float32(tau), k)
            out[f"labels/{v}/{name}"] = new
            out[f"moved/{v}/{name}"] = moved
    return out


def compare(out):
    out = {key: np.asarray(x) for key, x in out.items()}
    res = {key[:-len("/slot")]: bool(np.array_equal(x, out[key[:-4] + "dense"]))
           for key, x in out.items() if key.endswith("/slot")}
    # not vacuous: candidates exist, some delta is nonzero, some move made
    res["nontrivial"] = bool(out["cand"].any()
                             and (out["delta/gain/slot"] != 0).any()
                             and out["moved/jet/slot"].any())
    return res


results = {}
cases = [make_graph(s) for s in (3, 11, 29)]

for i, (g, k, labels, locked, tau) in enumerate(cases):
    ev = edge_view_from_graph(g)
    fn = jax.jit(lambda lab, lk: cell(SingleComm(g.n), ev, lab, lk, tau, k))
    results[f"single/g{i}"] = compare(fn(jnp.asarray(labels),
                                         jnp.asarray(locked)))

for P_ in (1, 8):
    mesh, _ = make_pe_mesh(P_)
    sh = P("pe", None)
    for i, (g, k, labels, locked, tau) in enumerate(cases):
        sg = shard_graph(g, P_, mesh=mesh)
        n_local = sg.n_local

        def body(src, dst, ew, nw, owned, gstart, lab, lk):
            ev = _sharded_edge_view(src[0], dst[0], ew[0], nw[0], owned[0],
                                    n_local)
            cm = AllGatherComm(gstart[0], n_local, sg.n_real)
            out = cell(cm, ev, lab[0], lk[0], tau, k)
            return {key: x[None] for key, x in out.items()}

        fn = jax.jit(shard_map(body, mesh=mesh,
                               in_specs=(sh,) * 5 + (P("pe"), sh, sh),
                               out_specs=sh))
        lk = labels_to_sharded(sg, jnp.asarray(locked.astype(np.int32))) > 0
        results[f"allgather/P{P_}/g{i}"] = compare(fn(
            sg.src, sg.dst, sg.ew, sg.nw, owned_mask(sg), sg.vtx_start,
            labels_to_sharded(sg, jnp.asarray(labels)), lk))

        hsg, perm = shard_graph_halo(g, P_)

        def hbody(src, code, hgid, ew, nw, gid, owned, inv, gstart, lab, lk):
            ev = halo_edge_view(src[0], code[0], hgid[0], ew[0], nw[0],
                                gid[0], owned[0])
            cm = HaloComm(P_, hsg.h_local, hsg.n_local, hsg.n_real,
                          gstart=gstart[0], inv_perm=inv[0])
            out = cell(cm, ev, lab[0], lk[0], tau, k)
            return {key: x[None] for key, x in out.items()}

        hfn = jax.jit(shard_map(hbody, mesh=mesh,
                                in_specs=(sh,) * 8 + (P("pe"), sh, sh),
                                out_specs=sh))
        hlk = halo_labels_to_sharded(hsg, perm,
                                     locked.astype(np.int32)) > 0
        results[f"halo/P{P_}/g{i}"] = compare(hfn(
            hsg.src, hsg.dst_code, hsg.head_gid, hsg.ew, hsg.nw, hsg.my_gid,
            hsg.owned, hsg.inv_perm, hsg.gstart,
            halo_labels_to_sharded(hsg, perm, labels), hlk))

# batched: the three graphs share one bucket (ragged sizes, padded slots);
# one k, labels and lock per slot, padded with label 0 / unlocked
bg = from_graphs([c[0] for c in cases])
k_b = max(c[1] for c in cases)
lab_b = np.zeros((bg.b, bg.n), np.int32)
lk_b = np.zeros((bg.b, bg.n), bool)
for i, (g, k, labels, locked, tau) in enumerate(cases):
    lab_b[i, :g.n] = labels
    lk_b[i, :g.n] = locked


def per_slot(col, src, ew, nw, n_real, lab, lk):
    ev = _batched_edge_view(col, src, ew, nw, n_real, bg.n)
    return cell(SingleComm(bg.n), ev, lab, lk, 0.5, k_b)


bfn = jax.jit(jax.vmap(per_slot))
results["batched/B3"] = compare(bfn(bg.col, bg.src, bg.ew, bg.nw, bg.n_real,
                                    jnp.asarray(lab_b), jnp.asarray(lk_b)))
print("RESULT::" + json.dumps(results))
"""

CELLS = ("single", "allgather/P1", "allgather/P8", "halo/P1", "halo/P8",
         "batched")


@pytest.fixture(scope="module")
def parity():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT::"):
            return json.loads(line[len("RESULT::"):])
    raise AssertionError(f"no RESULT line in output: {proc.stdout[-2000:]}")


@pytest.mark.parametrize("comm", CELLS)
def test_dense_layout_matches_slot(parity, comm):
    """Afterburner deltas (both orders) and every variant's move are
    bit-identical in the two layouts, on every graph of the cell."""
    runs = {name: r for name, r in parity.items() if name.startswith(comm)}
    assert runs, sorted(parity)
    for name, r in runs.items():
        assert r.pop("nontrivial"), name
        bad = [key for key, eq in r.items() if not eq]
        assert not bad, (name, bad)
        assert len(r) == 2 + 2 * 4, (name, sorted(r))


def test_fuzz_counterexample_same_in_both_layouts():
    """The example that falsifies ``test_afterburner_round_fuzzed`` (jet,
    gseed 0, k 2, seed 0, tau 1.0: the cut goes from 71 to 74) has the same
    outcome in the dense layout: this change neither causes nor hides it."""
    from test_schedule_property import make_random_graph

    from repro.refine import engine
    from repro.refine.comm import SingleComm, edge_view_from_graph
    from repro.refine.gain import make_gain
    from repro.refine.variants import resolve_variant

    g = make_random_graph(np.random.default_rng(0))
    k = 2
    labels = jax.random.randint(jax.random.PRNGKey(0), (g.n,), 0, k,
                                dtype=jnp.int32)
    ev = edge_view_from_graph(g)
    cm = SingleComm(g.n)
    max_deg = int(np.diff(np.asarray(g.row_ptr)).max())
    out = {}
    for kind in ("jnp", "pallas"):
        gb = make_gain(kind, ev, k, max_deg, interpret=True)
        new, moved = resolve_variant("jet").move(
            cm, gb, ev, labels, jnp.zeros(g.n, bool), jnp.float32(1.0), k)
        out[kind] = (float(engine.cut_of(cm, ev, labels)),
                     float(engine.cut_of(cm, ev, new)),
                     np.asarray(new), np.asarray(moved))
    assert out["jnp"][:2] == out["pallas"][:2] == (71.0, 74.0)
    assert np.array_equal(out["jnp"][2], out["pallas"][2])
    assert np.array_equal(out["jnp"][3], out["pallas"][3])


@pytest.mark.parametrize("gain,layout", [("pallas", "dense"),
                                         ("jnp", "slot")])
def test_level_backends_name_the_layout(gain, layout):
    """``level_backends`` reports the afterburner's layout per level: dense
    beside the Pallas gain, slot beside the jnp gain."""
    from repro.distributed import dpartition
    from repro.graphs import grid2d

    res = dpartition(grid2d(12, 12), k=4, P=1, seed=0, refiner="djet",
                     gain=gain, max_inner=4, coarsen_until=48)
    assert len(res.level_backends) == res.levels
    for lv in res.level_backends:
        assert lv["gain"] == gain, res.level_backends
        assert lv["afterburner"] == layout, res.level_backends
