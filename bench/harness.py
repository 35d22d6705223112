"""One run of one benchmark cell, driven by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs/<config>.json``: the graph, the
partitioner settings and the limits of the check) and a traffic mix
(``traffic/<traffic>.json``: a data file of parameters whose ``driver``
key names the code that runs it, ``drivers/<driver>.py``).  Every metric,
end-to-end or per-layer, is a reader ``metrics/<name>.py``.  Adding any of
these is a new file plus a new manifest entry; a new mix of an existing
driver is a data file alone.

A driver module has a ``job`` (the program call) and two functions that
are handed it (the driver's own ``job``, or a broken one in the tests):

  setup(config, mix, seed, job, timing) -> state
           build the inputs from the seed and run the warm-up jobs, so
           that every program the window uses compiles here; the state
           holds ``state["warm"]``, a list of answers;
  window(state, seconds, job, timing, after_job) -> [answer]
           the measured window; calls ``after_job()`` after each job.

An answer is a dict: ``graph`` (the check's ``(n, a, b, w)``) and ``out``
(``labels``, ``cut``, ``imbalance``, ``timings``, ``level_backends``; None
where the job raised).  Any other key of a window answer, such as a job's
``start`` and ``end`` (seconds from the window's start) or a request's
``arrival``, reaches the metric readers with the job, so that a reader can
take a latency.

A run:

  set-up   the driver's ``setup``, with JAX's persistent compile cache on;
  window   the driver's ``window``;
  check    every answer, warm-up and window, against ``check.py``, once
           the window has closed and the memory peak is read.

``--trace 0`` prints the end-to-end metrics.  ``--trace 1`` runs the same
window with the program's phase timer on and the profiler over its first
jobs (``TRACE_SECONDS``), and prints the per-layer metrics.
"""

from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

from bench import check, tracereduce

ROOT = Path(__file__).resolve().parents[1]
# the profiler records the window's first whole jobs that span this many
# seconds: on a v5e, one 22.5 s job of 2^17 vertices traces whole, while
# 22 s of back-to-back 2^15 jobs (about 285,000 device ops a second) lost
# their later events
TRACE_SECONDS = 2.0
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- manifest

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, manifest_path: Path):
    """``(cell, config, mix, end_to_end, per_layer)`` of one workload, each
    metric list cut to the metrics the cell reports.  Files are found by
    name under the manifest's directory."""
    manifest = load_json(manifest_path)
    root = manifest_path.parent
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {manifest_path}")
    cell = cells[name]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(root / entry["file"])
    mix = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")

    def reports(metric):
        return name in metric.get("workloads", [name])

    return (cell, config, mix,
            [m for m in manifest["end_to_end"] if reports(m)],
            [m for m in manifest["per_layer"] if reports(m)])


def load_module(kind: str, name: str, root: Path = ROOT):
    """``bench/<kind>/<name>.py`` as a module."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    return load_module("metrics", metric, root).read


def load_driver(mix: dict, root: Path = ROOT):
    """The module ``bench/drivers/<driver>.py`` that the mix names."""
    return load_module("drivers", mix["driver"], root)


# ------------------------------------------------------------------ set-up

def require_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache, in one fixed directory inside the
    checkout (the path is part of the cache key), whatever the environment
    names, so that two checkouts share nothing.  Every program is cached,
    however quickly it compiled, so that a second run of a seed finds all
    of them."""
    import jax

    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Sums the durations of JAX's ``/jax/core/compile/*`` events (tracing,
    lowering and XLA compilation, or a persistent-cache read)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.events += 1


class Tracer:
    """The profiler over the window's first jobs: started before the
    window, stopped after the first job that ends ``TRACE_SECONDS`` or
    more after the start, or when the window closes."""

    def __init__(self, on: bool):
        self.on, self.seconds = on, None
        if on:
            import jax

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self.t0 = time.perf_counter()

    def after_job(self) -> None:
        if self.on and time.perf_counter() - self.t0 >= TRACE_SECONDS:
            self.stop()

    def stop(self) -> None:
        if self.on:
            import jax

            self.seconds = time.perf_counter() - self.t0
            jax.profiler.stop_trace()
            self.on = False


# --------------------------------------------------------------------- run

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, wrap_job=None, require_tpu: bool = True,
             manifest_path: Path = ROOT / "BENCHMARK.json") -> dict:
    """One run; returns the result line as a dict whose last key is
    ``checks``.  ``wrap_job`` (tests) wraps the driver's ``job``."""
    cell, config, mix, e2e, per_layer = load_cell(workload, manifest_path)
    root = manifest_path.parent
    driver = load_driver(mix, root)
    job = wrap_job(driver.job) if wrap_job else driver.job
    import jax

    devices = (require_chips(cell["chips"]) if require_tpu
               else jax.devices()[:cell["chips"]])
    cache_dir = enable_compile_cache()
    clock = CompileClock()

    state = driver.setup(config, mix, seed, job, trace)
    setup_compile_s, setup_compiles = clock.seconds, clock.events
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    warm = state["warm"]
    log(f"setup_s={setup_s} compile_s={setup_compile_s} "
        f"compile_events={setup_compiles} cache={cache_dir} level_backends="
        f"{warm[0]['out']['level_backends'] if warm[0]['out'] else None}")

    tracer = Tracer(trace)
    window = driver.window(state, seconds, job, trace, tracer.after_job)
    window_s = time.perf_counter() - t_window
    window_compiles = clock.events - setup_compiles
    tracer.stop()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    # ---- the check, once the window has closed
    part, limits = config["partition"], config["limits"]
    k, eps = part["k"], part["eps"]
    answers = warm + window
    readings = [None if a["out"] is None else
                check.check_answer(a["graph"], k, eps, a["out"]["labels"],
                                   a["out"]["cut"], a["out"]["imbalance"])
                for a in answers]
    read = [r for r in readings if r is not None]
    numbers = check.worst(read) if read else None
    sound = [r is not None and check.verdict(r, limits) for r in readings]
    in_window = sound[len(warm):]
    correct = bool(window) and all(sound)
    done = [{**a["out"], **{k: v for k, v in a.items()
                            if k not in ("graph", "out")},
             "cut_ref": r["cut_ref"]}
            for a, r in zip(window, readings[len(warm):]) if r is not None]

    run = {"jobs": done, "window_s": window_s, "setup_s": setup_s,
           "setup_compile_s": setup_compile_s, "trace": None,
           "device_kind": devices[0].device_kind}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    line = {"correct": correct, "attempted": len(window),
            "failed": in_window.count(False)}
    if trace:
        tr = tracereduce.summarize(TRACE_DIR, [j["timings"] for j in done],
                                   tracer.seconds)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        run["trace"] = tr
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["top_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    metrics = {}
    for m in (per_layer if trace else e2e):
        value = load_reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    line["level_backends"] = done[0]["level_backends"] if done else []
    line["window_compiles"] = window_compiles
    line["checks"] = {name: {"value": numbers[name] if numbers else None,
                             "limit": limits[name]}
                      for name in check.NUMBERS}
    return line


def print_line(line: dict) -> None:
    """The checks as the last lines of standard error, then the result as
    the last line of standard output."""
    for name, c in line["checks"].items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
