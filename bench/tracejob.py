#!/usr/bin/env python3
"""Trace one job of a one-shot cell on the chip and split its time by the
program's own spans, device scopes and counters.

    python3 bench/tracejob.py --workload rgg2d-n17-k16.oneshot --seed 7 \
        --out tracejob.json

Not part of a benchmark run.  Set-up is the cell's (the driver's graph
and warm-up job, JAX's compile cache in the checkout); then one job runs
untraced, one under the profiler, one untraced again, all with the phase
timer on.  Printed as one JSON line (and written to ``--out``):

* ``jobs_s``: the three jobs' wall seconds, so the traced one can be set
  against the two around it;
* ``counters``: the traced job's host reads per site and dispatches per
  program (``repro.telemetry``);
* ``rungs_s``: device seconds of the refine phase's level programs
  (``programtrace.LEVEL_PROGRAMS``) per rung scope, each HLO op's self
  time counted toward its innermost scope (``None``: in no scope), from
  xprof's ``hlo_stats``; ``refine_programs_s``, the programs' device time
  on the trace's program line, and ``level_programs_s`` each level's;
* ``idle_gaps``: the longest gaps in the job, named by the program spans
  open at their middle;
* ``clock``: each cluster round's launch (device program start minus the
  host's dispatch span start) and each ``host_read/moved`` span's end
  minus the end of the round it reads, median and extremes in seconds.

Exits 2, printing nothing, when JAX finds no TPU.
"""

import argparse
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, programtrace, tracereduce  # noqa: E402

TRACE_DIR = ROOT / ".bench_tracejob"


def spread(values) -> dict | None:
    if not values:
        return None
    return {"n": len(values), "median": statistics.median(values),
            "min": min(values), "max": max(values)}


def reduce(path: str, timings: dict) -> dict:
    spans, programs = programtrace.load(path)
    devices, _ = tracereduce.load(TRACE_DIR, ())
    first = min(devices,
                key=lambda d: int(d[len(tracereduce.DEVICE_PLANE):]))
    jobs = [s for s in spans if s[0] == "job"]
    label = programtrace.labeller(spans, [timings])
    gaps = programtrace.idle_gaps(
        tracereduce.busy_intervals(devices[first]), jobs[0][1], jobs[0][2],
        label)

    refine = [p for p in programs
              if any(n in p[0] for n in programtrace.LEVEL_PROGRAMS)]
    ids = {programtrace.program_id(p[0]) for p in refine}
    rows = [r for r in programtrace.hlo_op_rows(path)
            if r["program_id"] in ids]
    per_level: dict = {}  # coarsest level first, as the V-cycle runs them
    for name, start, end in refine:
        per_level[name] = per_level.get(name, 0.0) + (end - start) * 1e-9
    rungs = programtrace.scope_self_s((r["op_name"], r["self_s"])
                                      for r in rows)

    rounds = [p for p in programs if "cluster_round" in p[0]]
    launch = programtrace.paired_offsets(
        [s for s in spans if s[0] == "cluster_round"], rounds)
    read = programtrace.paired_offsets(
        [s for s in spans if s[0] == "host_read/moved"], rounds)
    return {
        "rungs_s": {str(k): v for k, v in rungs.items()},
        "refine_programs_s": sum(p[2] - p[1] for p in refine) * 1e-9,
        "level_programs_s": list(per_level.values()),
        "idle_gaps": gaps,
        "busy_s": tracereduce.busy_ns(devices[first]) * 1e-9,
        "job_trace_s": (jobs[0][2] - jobs[0][1]) * 1e-9,
        "clock": {"launch_s": spread([a for a, _ in launch]),
                  "read_s": spread([b for _, b in read])},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell, config, mix, _, _ = harness.load_cell(args.workload,
                                                ROOT / "BENCHMARK.json")
    try:
        harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        harness.log(f"no result: {e}")
        return 2
    import jax

    from repro import telemetry

    harness.enable_compile_cache()
    driver = harness.load_driver(mix)
    state = driver.setup(config, mix, args.seed, driver.job, True)
    part = config["partition"]

    def job():
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("job"), \
                telemetry.record() as rec:
            out = driver.job(state["g"], part, 0, True)
        return time.perf_counter() - t0, out, rec.as_dict()

    before, _, _ = job()
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    traced, out, counters = job()
    jax.profiler.stop_trace()
    after, _, _ = job()

    line = {"workload": args.workload, "seed": args.seed,
            "device_kind": jax.devices()[0].device_kind,
            "jobs_s": {"untraced_before": before, "traced": traced,
                       "untraced_after": after},
            "cut": out["cut"], "timings": out["timings"],
            "counters": counters,
            **reduce(programtrace.xplane_path(TRACE_DIR), out["timings"])}
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    text = json.dumps(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
