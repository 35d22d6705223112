"""A whole run of the harness on the CPU, at a tiny size: its control flow
and the shape of its last line, its refusal without a TPU, and ``correct``
coming out false with the timed path broken underneath.  Also the control
of the check (``control.py``) at a size a test run holds."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import control, harness, tracereduce  # noqa: E402

CELL = json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]


def tiny_root(root: Path, n: int) -> Path:
    """The committed manifest, with the rgg2d graph cut to ``n`` vertices
    (the CPU runs the Pallas kernels in interpret mode)."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs").mkdir(parents=True)
    for sub in ("traffic", "drivers", "metrics"):
        os.symlink(ROOT / "bench" / sub, root / "bench" / sub)
    for entry in manifest["configs"]:
        config = json.loads((ROOT / entry["file"]).read_text())
        if config["graph"]["generator"] == "rgg2d":
            config["graph"]["n"] = n
        (root / entry["file"]).write_text(json.dumps(config))
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root / "BENCHMARK.json"


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench_root"), 1024)


def run(manifest, trace=False, wrap_job=None, seconds=0.5):
    return harness.run_cell(CELL, 2**31 + 77, seconds, trace, 0.0,
                            wrap_job=wrap_job, require_tpu=False,
                            manifest_path=manifest)


RUN_ON_CPU = """
import sys, time
t_start = time.perf_counter()
sys.path[:0] = [sys.argv[2], sys.argv[2] + "/src"]
from pathlib import Path
from bench import harness
line = harness.run_cell(sys.argv[3], 2**31 + 77, 0.5, False, t_start,
                        require_tpu=False, manifest_path=Path(sys.argv[1]))
harness.print_line(line)
"""


def test_a_sound_run_and_its_last_line(tiny_manifest):
    """A whole run in a fresh process, as the benchmark's runs are: nothing
    compiles in the window, and the last lines are the checks on standard
    error and the result on standard output."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    p = subprocess.run(
        [sys.executable, "-c", RUN_ON_CPU, str(tiny_manifest), str(ROOT),
         CELL], capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 1
    assert line["failed"] == 0 and line["window_compiles"] == 0
    assert set(line["metrics"]) == {"job_s", "cut", "setup_s"}
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    assert p.stderr.strip().splitlines()[-1].startswith(
        "check balance_excess")


def test_a_traced_run_reports_the_per_layer_metrics(tiny_manifest,
                                                    monkeypatch, tmp_path):
    # the CPU trace has no TPU plane; the reduction is tested on its own
    def summary(profiler_dir, timings, window_s):
        assert len(timings) >= 1 and all(timings)
        return {"busy_s": window_s / 2, "window_s": window_s,
                "top_ops": [["gather.1", 0.1]],
                "idle_gaps": [["job/refine", 0.01]], "events": [],
                "chips": 1}

    monkeypatch.setattr(tracereduce, "summarize", summary)
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    line = run(tiny_manifest, trace=True)
    assert line["correct"]
    assert set(line["metrics"]) == {"coarsen_s", "init_s", "refine_s",
                                    "device_idle", "compile_s"}
    assert line["metrics"]["device_idle"]["value"] == pytest.approx(50.0)
    assert line["device"]["busy_s"] > 0 and "breakdown" in line


def altered(out):
    lab = out["labels"].copy()
    lab[: len(lab) // 8] = (lab[: len(lab) // 8] + 1) % 16
    return {**out, "labels": lab}


def half_left_out(out):
    return {**out, "labels": out["labels"][: len(out["labels"]) // 2]}


def state_unchanged(out):
    return {**out, "labels": np.zeros_like(out["labels"])}


@pytest.mark.parametrize("fault", [altered, half_left_out, state_unchanged])
def test_a_broken_timed_path_is_not_correct(tiny_manifest, fault):
    def broken(job):
        return lambda g, part, seed, timing: fault(job(g, part, seed, timing))

    line = run(tiny_manifest, wrap_job=broken)
    assert not line["correct"]
    assert line["failed"] == line["attempted"]


def test_no_tpu_no_result():
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2 and p.stdout == ""


def test_the_control_fails_where_the_reference_passes(tmp_path):
    """Block capacities past 256 (here 8,192 vertices in 16 blocks): a
    bfloat16 counter stops at 256, so the first block takes everything."""
    res = control.readings(CELL, [3, 4], tiny_root(tmp_path, 8192))
    assert all(r["correct"] for r in res["float32"])
    assert not any(r["correct"] for r in res["bfloat16"])
    assert all(r["balance_excess"] > 0 for r in res["bfloat16"])
