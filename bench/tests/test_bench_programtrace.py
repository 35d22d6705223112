"""The program's spans and scopes in a trace, on synthetic events: device
time per rung scope counted toward the innermost scope only, idle gaps
named by the nested program spans with the old labels where none is open,
host spans paired with the device programs they wait for, and the
benchmark's readers reading the same values from jobs that carry the
program's counters."""

import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import programtrace, tracereduce  # noqa: E402


PROGRAM = "jit(refine_allgather)/while/body/closed_call/while/body"
# (op_name, self seconds) of HLO ops, as xprof's hlo_stats gives them
ROWS = [
    (f"{PROGRAM}/move/gain/gather:", 100.0),
    (f"{PROGRAM}/move/afterburner/add:", 50.0),
    (f"{PROGRAM}/move/select_n:", 20.0),
    (f"{PROGRAM}/rebalance/while/body/gain/add:", 60.0),
    (f"{PROGRAM}/rebalance/while/body/max:", 40.0),
    (f"{PROGRAM}/cut/reduce:", 30.0),
    (f"{PROGRAM}/add:", 10.0),     # in no scope
    ("", 5.0),                     # no metadata
]


def test_rung_time_counts_toward_the_innermost_scope_only():
    got = programtrace.scope_self_s(ROWS)
    assert got == {"gain": 160.0, "afterburner": 50.0, "move": 20.0,
                   "rebalance": 40.0, "cut": 30.0, None: 15.0}
    # the rungs and the rest add up to the ops' own time, each op once
    assert sum(got.values()) == sum(s for _, s in ROWS)
    assert programtrace.innermost_scope("a/move/gain/b") == "gain"
    assert programtrace.innermost_scope("a/gainful/b") is None


def test_a_program_without_scopes_has_no_rung_time():
    plain = [("jit(refine_allgather)/while/body/add:", 3.0), ("", 1.0)]
    assert programtrace.scope_self_s(plain) == {None: 4.0}
    assert programtrace.program_id("jit_refine_allgather(4211)") == "4211"


# a job with the program's spans: dpartition > coarsen > coarsen/level >
# host_read/nc, then refine > refine/level; device busy [0, 100),
# [200, 300), [600, 700), [900, 985)
SPANS = [
    ("job", 0, 1000),
    ("dpartition", 10, 990),
    ("coarsen", 10, 400),
    ("coarsen/level", 20, 390),
    ("host_read/nc", 120, 190),
    ("refine", 500, 980),
    ("refine/level", 510, 970),
]
TIMINGS = [{"coarsen_s": 400e-9, "init_s": 100e-9, "refine_s": 480e-9}]
BUSY = [(0, 100), (200, 300), (600, 700), (900, 985)]


def test_span_paths_nest_by_containment():
    paths = [p for p, _, _, _ in programtrace.span_paths(SPANS)]
    assert paths == [
        "job", "job/dpartition", "job/dpartition/coarsen",
        "job/dpartition/coarsen/coarsen/level",
        "job/dpartition/coarsen/coarsen/level/host_read/nc",
        "job/dpartition/refine", "job/dpartition/refine/refine/level"]


def test_idle_gaps_are_named_by_the_program_spans():
    label = programtrace.labeller(SPANS, TIMINGS)
    gaps = programtrace.idle_gaps(BUSY, 0, 1000, label)
    # [300, 600), [700, 900), [100, 200), [985, 1000)
    assert gaps == [
        ["job/dpartition", pytest.approx(300e-9)],
        ["job/dpartition/refine/refine/level", pytest.approx(200e-9)],
        ["job/dpartition/coarsen/coarsen/level/host_read/nc",
         pytest.approx(100e-9)],
        ["job", pytest.approx(15e-9)],
    ]


def test_without_program_spans_the_old_labels_stay():
    jobs = [s for s in SPANS if s[0] == "job"]
    label = programtrace.labeller(jobs, TIMINGS)
    new = programtrace.idle_gaps(BUSY, 0, 1000, label)
    old = tracereduce.idle_gaps(BUSY, 0, 1000,
                                tracereduce.phase_spans(jobs, TIMINGS))
    assert new == old
    assert [g[0] for g in new] == ["job/init", "job/refine", "job/coarsen",
                                   "job"]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_readers_read_the_same_values_from_jobs_with_counters():
    timings = {"coarsen_s": 0.5, "init_s": 0.25, "refine_s": 2.0}
    job = {"timings": timings, "cut_ref": 10.0}
    run = {"jobs": [job, dict(job)], "window_s": 6.0, "setup_s": 50.0,
           "setup_compile_s": 20.0, "device_kind": "TPU v5 lite",
           "trace": {"busy_s": 3.0, "window_s": 4.0,
                     "events": [("fusion.1", 0, 100, ""),
                                ("fusion.2", 50, 150, "")]}}
    with_counters = dict(run, jobs=[
        dict(job, counters={"host_reads": {"nc": 1}, "dispatches": {}})
        for job in run["jobs"]])
    for name in ("coarsen_s", "init_s", "refine_s", "device_idle",
                 "gain_kernel_roofline", "compile_s", "job_s", "cut",
                 "setup_s"):
        read = _reader(name)
        assert read(with_counters) == read(run), name


def test_spans_pair_with_the_programs_they_wait_for():
    dispatch = [("cluster_round", 100, 120), ("cluster_round", 400, 420)]
    reads = [("host_read/moved", 121, 390), ("host_read/moved", 421, 700)]
    programs = [("jit_cluster_round(7)", 110, 380),
                ("jit_cluster_round(7)", 415, 690)]
    starts = [a for a, _ in programtrace.paired_offsets(dispatch, programs)]
    ends = [b for _, b in programtrace.paired_offsets(reads, programs)]
    assert starts == [pytest.approx(10e-9), pytest.approx(15e-9)]
    assert ends == [pytest.approx(10e-9), pytest.approx(10e-9)]
