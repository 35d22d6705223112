#!/usr/bin/env python3
"""Smoke run of the distributed V-cycle on a TPU.

    python chip_smoke.py             # one chip: phases (a)-(d) below
    python chip_smoke.py --chips 4   # four chips: P = 4 against P = 1 only

One chip, every phase in this process (the CPU reference runs in a child
that is held to the CPU backend and never touches the chip):

  (a) grid2d_256k — ``dpartition`` at P = 1, k = 16, eps = 0.03, d4xjet,
      with the Pallas gain kernel, under the all-gather and the halo
      protocol, and with the jnp gain path; each result has labels in
      [0, k), imbalance <= eps and a cut equal to the one numpy recomputes
      from the labels, and all three label arrays are bit-identical;
  (b) rmat_21 — the same runs and checks; cut for now, see ``CHIP_GRAPHS``;
  (c) a served stream — two dozen requests through ``PartitionService``
      on grid2d_1k / rmat_9 (label-buffer donation is on, as it is on
      every TPU); every future resolves and equals solo ``partition``;
  (d) CPU reference — small graphs partitioned on the CPU backend in the
      child and again here; the chip's labels equal the CPU's bit for bit.

The phases run side by side in threads, and so do the halo and jnp runs
of (a) once its first run has compiled the coarsening: compiling is host
work, and most of a cold run is compiling; the chip runs one program at a
time either way.

``--chips 4`` runs ``dpartition`` on the phase (a) graph at P = 4
(all-gather and halo) and compares each bit for bit with P = 1 in the
same process — the P-invariance contract (DESIGN.md §1).

Every Pallas call on the path must run compiled (``interpret=False``); the
log names the gain and halo backend that the envelope rules chose at each
level.  Where ``JAX_COMPILATION_CACHE_DIR`` is unset, the compile cache is
``<repo>/.jax_cache``.  The script exits non-zero, and prints no result,
when JAX finds no TPU, when the repository is not beside it, or when any
check fails; its last line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
where N is the number of chips the run used (1, or 4 with ``--chips 4``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
SRC = HERE / "src"

K, EPS, SEED = 16, 0.03, 0
# The graphs of phases (a)/(b).  On a v5e, d4xjet refinement is bound by
# XLA's per-edge gathers (about 19 ns per lookup, ~10 lookups per inner
# iteration): one 321,597-vertex level of grid2d_8m took 72 s, so grid2d_8m
# itself (33.5 M edge slots) would take about half an hour per run, and
# the smoke makes three runs in a 1200 s limit.  grid2d_256k is the mesh
# that fits (PERF.md).  rmat_21, phase (b), is cut as well: LP clustering
# cannot cluster isolated vertices (ROADMAP 1.2b), so its hierarchy stops
# near 0.8 M vertices and the sequential initial partitioner runs on all
# of them.
CHIP_GRAPHS = ("grid2d_256k",)
# (graph, refiner) pairs of the CPU reference: d4xjet takes minutes on a
# CPU above ~8k vertices, so the ~16k-vertex graph runs djet
CPU_REF_CASES = (("grid2d_1k", "d4xjet"), ("rmat_9", "d4xjet"),
                 ("rgg2d_16k", "djet"))
# small graphs, the serving case: with rgg2d_16k / rmat_14 the stream had
# not finished after 1000 s beside the other phases on a v5e (its
# dispatcher compiled for 467 s, one program per rung and bucket)
SERVE_GRAPHS = ("grid2d_1k", "rmat_9")
SERVE_REQUESTS = 24
SERVE_SEEDS = 3


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def log(*parts) -> None:
    print(*parts, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------------------
# observation: compile time, device memory, Pallas interpret flags
# --------------------------------------------------------------------------

class Recorder:
    """Sums JAX's compile-event durations and records the ``interpret``
    flag of every ``pallas_call`` that is traced while it is installed."""

    def __init__(self):
        import jax
        from jax.experimental import pallas as pl

        self.compile_s = 0.0
        # compile seconds per phase: the phase is a thread-local tag set by
        # ``Phase``; compiles on untagged threads (the service's
        # dispatcher) count under "other"
        self.phase_compile_s: dict[str, float] = {}
        self.tag = threading.local()
        self.last_compiled = None
        self._stop, self._lock = threading.Event(), threading.Lock()
        self.backend_compile_s: dict[str, float] = {}
        self.cache_hits = 0
        self.interpret_flags: list[bool] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)
        real = pl.pallas_call

        def recording_pallas_call(*args, **kwargs):
            with self._lock:
                self.interpret_flags.append(
                    bool(kwargs.get("interpret", False)))
            return real(*args, **kwargs)

        pl.pallas_call = recording_pallas_call

    def phase_compile(self, name: str) -> float:
        with self._lock:
            return self.phase_compile_s.get(name, 0.0)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile/"):
            phase = getattr(self.tag, "phase", "other")
            with self._lock:
                self.compile_s += duration
                self.phase_compile_s[phase] = (
                    self.phase_compile_s.get(phase, 0.0) + duration)
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            with self._lock:
                self.last_compiled = name
                self.backend_compile_s[name] = (
                    self.backend_compile_s.get(name, 0.0) + duration)

    def start_heartbeat(self, every_s: float = 60.0) -> None:
        """Log elapsed and compile seconds and the last compiled program
        every ``every_s`` seconds, so a run cut by its time limit still
        shows where the time went."""
        t0 = time.perf_counter()

        def beat():
            while not self._stop.wait(every_s):
                with self._lock:
                    if not self._stop.is_set():
                        log(f"  [heartbeat] t={time.perf_counter() - t0:.0f}s "
                            f"compile_s={self.compile_s:.1f} "
                            f"last_compiled={self.last_compiled} "
                            f"by_phase={json.dumps(self.phase_compile_s)}")

        threading.Thread(target=beat, daemon=True).start()

    def stop_heartbeat(self) -> None:
        """No heartbeat line is printed after this returns."""
        with self._lock:
            self._stop.set()

    def _on_count(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def log_slowest_compiles(self, top: int = 15) -> None:
        """XLA compile seconds per program name (summed over its shapes),
        slowest first; a persistent-cache hit counts its retrieval time."""
        ranked = sorted(self.backend_compile_s.items(), key=lambda kv: -kv[1])
        log(f"compile: {len(ranked)} programs, {self.cache_hits} persistent "
            f"cache hits; slowest: " + ", ".join(
                f"{name}={sec:.2f}s" for name, sec in ranked[:top]))

    def check_compiled(self) -> None:
        n = len(self.interpret_flags)
        check(n > 0, "no Pallas kernel was traced on the smoke path")
        check(not any(self.interpret_flags),
              f"{sum(self.interpret_flags)} of {n} Pallas calls ran in "
              f"interpret mode")
        log(f"pallas: {n} traced calls, all interpret=False")


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class Phase:
    """Wall time, compile time and device peak bytes of one phase.

    Compile seconds are the phase's own (the compiles of the thread that
    runs it).  The peak is the device's since the process started, read at
    the phase's end: with phases side by side it bounds, not isolates, the
    phase's own footprint."""

    def __init__(self, name: str, rec: Recorder, devices):
        self.name, self.rec, self.devices = name, rec, devices

    def __enter__(self):
        self.rec.tag.phase = self.name
        self.t0 = time.perf_counter()
        self.c0 = self.rec.phase_compile(self.name)
        log(f"== phase {self.name}")
        return self

    def __exit__(self, exc_type, exc, tb):
        wall = time.perf_counter() - self.t0
        comp = self.rec.phase_compile(self.name) - self.c0
        peaks = [peak_bytes(d) for d in self.devices]
        status = "ok" if exc_type is None else f"FAILED ({exc_type.__name__}: {exc})"
        log(f"== phase {self.name} {status}: wall_s={wall} compile_s={comp} "
            f"peak_bytes_in_use={peaks}")
        return False


def run_side_by_side(rec: Recorder, devices, phases) -> None:
    """Run ``(name, fn)`` phases in threads, each under its own ``Phase``;
    wait for all, then re-raise the first failure."""
    def one(name, fn):
        with Phase(name, rec, devices):
            fn()

    with ThreadPoolExecutor(max_workers=len(phases)) as pool:
        futs = [pool.submit(one, name, fn) for name, fn in phases]
    errors = [f.exception() for f in futs if f.exception() is not None]
    if errors:
        raise errors[0]


# --------------------------------------------------------------------------
# checks
# --------------------------------------------------------------------------

def host_cut(g, labels: np.ndarray) -> float:
    """Edge cut recomputed in numpy (float64) from the labels."""
    src = np.asarray(g.src)
    col = np.asarray(g.col)
    ew = np.asarray(g.ew, dtype=np.float64)
    return float(ew[labels[src] != labels[col]].sum() / 2.0)


def host_imbalance(g, labels: np.ndarray, k: int) -> float:
    nw = np.asarray(g.nw, dtype=np.float64)
    bw = np.bincount(labels, weights=nw, minlength=k)
    return float(bw.max() / (nw.sum() / k) - 1.0)


def check_result(tag: str, g, res, k: int, eps: float) -> np.ndarray:
    lab = np.asarray(res.labels)
    check(lab.shape == (g.n,), f"{tag}: labels shape {lab.shape} != ({g.n},)")
    check(lab.min() >= 0 and lab.max() < k, f"{tag}: labels outside [0, {k})")
    imb = host_imbalance(g, lab, k)
    check(res.imbalance <= eps and imb <= eps,
          f"{tag}: imbalance {res.imbalance} (host {imb}) > eps {eps}")
    cut = host_cut(g, lab)
    check(cut == res.cut, f"{tag}: host cut {cut} != reported cut {res.cut}")
    return lab


def log_backends(tag: str, res) -> None:
    for j, lv in enumerate(res.level_backends):
        log(f"  {tag} level {j}: n={lv['n']} gain={lv['gain']} "
            f"halo={lv['halo']}")


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def run_dpartition(tag, g, *, P, gain, halo, refiner="d4xjet", rec=None):
    from repro.distributed import dpartition

    phase = getattr(rec.tag, "phase", "other") if rec else None
    c0 = rec.phase_compile(phase) if rec else 0.0
    t0 = time.perf_counter()
    res = dpartition(g, k=K, P=P, eps=EPS, seed=SEED, refiner=refiner,
                     gain=gain, halo=halo, timing=True)
    wall = time.perf_counter() - t0
    comp = (rec.phase_compile(phase) - c0) if rec else 0.0
    log(f"  {tag}: P={res.P} levels={res.levels} cut={res.cut} "
        f"imbalance={res.imbalance} wall_s={wall} compile_s={comp} "
        f"phases={json.dumps(res.timings)}")
    return res


def phase_graph(name: str, rec: Recorder, devices) -> None:
    """(a)/(b): pallas all-gather, then pallas halo and jnp all-gather side
    by side (the first run has compiled the coarsening they share), at
    P=1."""
    from repro.graphs import generate

    t0 = time.perf_counter()
    g = generate(name)
    log(f"  {name}: n={g.n} m={g.m} generate_s={time.perf_counter() - t0}")
    labels = {}

    def one(gain, halo):
        tag = f"{name}/{gain}/{'halo' if halo else 'allgather'}"
        res = run_dpartition(tag, g, P=1, gain=gain, halo=halo, rec=rec)
        log_backends(tag, res)
        if gain == "pallas":
            check(any(lv["gain"] == "pallas" for lv in res.level_backends),
                  f"{tag}: no level ran the Pallas gain kernel")
        labels[tag] = check_result(tag, g, res, K, EPS)

    one("pallas", False)
    run_side_by_side(rec, devices, [
        (f"{name}/pallas/halo", lambda: one("pallas", True)),
        (f"{name}/jnp/allgather", lambda: one("jnp", False))])
    ref_tag = f"{name}/pallas/allgather"
    ref = labels[ref_tag]
    for tag, lab in labels.items():
        check(np.array_equal(lab, ref), f"{tag} labels differ from {ref_tag}")
    log(f"  {name}: {len(labels)} runs bit-identical")


def phase_serve(rec: Recorder) -> None:
    """(c): a served stream equals solo ``partition`` request by request."""
    from repro.core import PartitionConfig, partition
    from repro.graphs import generate
    from repro.refine import drivers
    from repro.serve import FlushPolicy, PartitionService

    check(drivers._donation_supported() or drivers.FORCE_DONATE,
          "label-buffer donation is off")
    cfg = PartitionConfig(k=K, eps=EPS)
    graphs = {name: generate(name) for name in SERVE_GRAPHS}
    plan = [(SERVE_GRAPHS[i % len(SERVE_GRAPHS)], (i // 2) % SERVE_SEEDS)
            for i in range(SERVE_REQUESTS)]
    t0 = time.perf_counter()
    with PartitionService(policy=FlushPolicy(batch_target=4),
                          mode="replay") as svc:
        futs = [svc.submit(graphs[name], config=cfg, seed=seed)
                for name, seed in plan]
    # results after the with block: its exit drains the tail bucket
    results = [f.result(timeout=600) for f in futs]
    log(f"  served {len(results)} requests in "
        f"{time.perf_counter() - t0} s; stats={json.dumps(svc.stats(), default=str)}")
    solo = {}
    for (name, seed), res in zip(plan, results):
        if (name, seed) not in solo:
            solo[name, seed] = partition(graphs[name], seed=seed, config=cfg)
        want = solo[name, seed]
        check(np.array_equal(np.asarray(res.labels), np.asarray(want.labels))
              and res.cut == want.cut,
              f"served {name} seed={seed} differs from solo partition")
    log(f"  all {len(results)} served results equal solo partition "
        f"({len(solo)} distinct requests)")


def cpu_reference(out_path: str) -> None:
    """Child mode: partition the CPU-reference cases on the CPU backend."""
    import jax

    from repro.graphs import generate

    check(jax.default_backend() == "cpu", "CPU reference left the CPU")
    out = {}
    for name, refiner in CPU_REF_CASES:
        t0 = time.perf_counter()
        res = run_dpartition(f"cpu/{name}", generate(name), P=1,
                             gain="pallas", halo=False, refiner=refiner)
        out[name] = np.asarray(res.labels)
        log(f"  cpu/{name}: {time.perf_counter() - t0} s")
    np.savez(out_path, **out)


def start_cpu_reference(tmp: str):
    out = os.path.join(tmp, "cpu_ref.npz")
    logf = open(os.path.join(tmp, "cpu_ref.log"), "w")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, str(Path(__file__).resolve()), "--cpu-reference",
         out], env=env, stdout=logf, stderr=subprocess.STDOUT)
    return proc, out, logf


def phase_cpu_reference(proc, out: str, logf, rec: Recorder) -> None:
    """(d): the chip's labels equal the CPU backend's bit for bit.  The
    chip side runs first, while the child computes the reference."""
    from repro.graphs import generate

    chip = {}
    for name, refiner in CPU_REF_CASES:
        tag = f"tpu/{name}"
        g = generate(name)
        res = run_dpartition(tag, g, P=1, gain="pallas", halo=False,
                             refiner=refiner, rec=rec)
        chip[name] = check_result(tag, g, res, K, EPS)
    rc = proc.wait(timeout=900)
    logf.flush()
    child_log = Path(logf.name).read_text()
    log("  cpu child log:\n" + "\n".join(
        "    " + ln for ln in child_log.splitlines()[-12:]))
    check(rc == 0, f"CPU reference child exited {rc}")
    ref = np.load(out)
    for name, refiner in CPU_REF_CASES:
        lab = chip[name]
        same = np.array_equal(lab, ref[name])
        log(f"  {name} ({refiner}): chip == cpu: {same} "
            f"(differing labels: {int((lab != ref[name]).sum())})")
        check(same, f"{name}: chip labels differ from the CPU reference")


def phase_four_chips(rec: Recorder) -> None:
    """``--chips 4``: P = 4 (all-gather, halo) against P = 1, bit for bit."""
    import jax

    from repro.distributed.dgraph import shard_graph
    from repro.distributed.dmultilevel import make_pe_mesh
    from repro.graphs import generate

    mesh, P = make_pe_mesh(4)
    check(set(mesh.devices.flat) == set(jax.devices()),
          f"PE mesh {mesh.devices} does not span {jax.devices()}")
    name = CHIP_GRAPHS[0]
    g = generate(name)
    sg = shard_graph(g, P, mesh=mesh)
    for leaf in (sg.src, sg.dst, sg.ew, sg.nw):
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == P
              and all(s.data.shape[0] == 1 for s in shards),
              f"sharded graph leaf {leaf.shape} is not split over {P} PEs")
    del sg
    log(f"  mesh spans {P} devices; sharded graph placed one PE per device")
    base = run_dpartition(f"{name}/P1", g, P=1, gain="pallas", halo=False,
                          rec=rec)
    ref = check_result(f"{name}/P1", g, base, K, EPS)
    for halo in (False, True):
        tag = f"{name}/P4/{'halo' if halo else 'allgather'}"
        res = run_dpartition(tag, g, P=P, gain="pallas", halo=halo, rec=rec)
        log_backends(tag, res)
        lab = check_result(tag, g, res, K, EPS)
        same = np.array_equal(lab, ref)
        log(f"  {tag} == P1: {same} (differing labels: "
            f"{int((lab != ref).sum())})")
        check(same, f"{tag} labels differ from P=1")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-reference", metavar="OUT_NPZ",
                    help=argparse.SUPPRESS)  # child mode of phase (d)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repository's src/repro is not beside "
              f"{Path(__file__).name}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    if args.cpu_reference:
        try:
            cpu_reference(args.cpu_reference)
        except SmokeFailure as e:
            print(f"chip_smoke cpu reference: FAILED: {e}", file=sys.stderr)
            return 1
        return 0

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX finds {len(devices)}", file=sys.stderr)
        return 1
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; jax {jax.__version__}; compile cache "
        f"{enable_compile_cache()}")
    rec = Recorder()
    rec.start_heartbeat()
    t_start = time.perf_counter()
    try:
        if args.chips == 4:
            with Phase("four_chips", rec, devices):
                phase_four_chips(rec)
        else:
            with tempfile.TemporaryDirectory() as tmp:
                proc, out, logf = start_cpu_reference(tmp)
                try:
                    run_side_by_side(rec, devices, [
                        *[(name, lambda name=name:
                           phase_graph(name, rec, devices))
                          for name in CHIP_GRAPHS],
                        ("serve", lambda: phase_serve(rec)),
                        ("cpu_reference", lambda: phase_cpu_reference(
                            proc, out, logf, rec))])
                finally:
                    if proc.poll() is None:
                        proc.kill()
                        proc.wait()
                    logf.close()
        rec.check_compiled()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        rec.stop_heartbeat()
    log(f"total wall_s={time.perf_counter() - t_start} "
        f"compile_s={rec.compile_s}")
    rec.log_slowest_compiles()
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
