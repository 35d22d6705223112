"""BENCHMARK.json: its keys, names, units and bounds, and every file it
names found by name."""

import json
import re
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import harness  # noqa: E402

MANIFEST = ROOT / "BENCHMARK.json"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


@pytest.fixture(scope="module")
def manifest():
    with open(MANIFEST) as f:
        return json.load(f)


def test_keys_names_and_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    for path in manifest["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", path)
        assert (ROOT / path).is_dir()
    assert all(LINE.match(w) for w in manifest["command"])
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [e["name"] for e in manifest["configs"] + manifest["workloads"]
             + metrics]
    assert len(names) == len(set(names))
    for e in manifest["configs"]:
        assert set(e) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(x) for x in [e["name"], *e["reduced"]])
        assert LINE.match(e["source"]) and LINE.match(e["why"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[x]) for x in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and LINE.match(w["why"])
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert LINE.match(m["layer"])


def test_every_metric_moves_a_metric_its_cells_report(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells)
           for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", e2e[m["moves"]]):
            assert cell in cells and cell in e2e[m["moves"]], (m, cell)
    for cell in cells:
        reported = [n for n, ws in e2e.items() if cell in ws]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m.get("workloads", e2e[m["moves"]])
                   for m in manifest["per_layer"])


def test_every_cell_finds_its_files_by_name(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    for c in manifest["configs"]:
        assert any(Path(c["file"]).parts[0] == p for p in manifest["paths"])
        with open(ROOT / c["file"]) as f:
            config = json.load(f)
        assert config["name"] == c["name"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
    for w in manifest["workloads"]:
        cell, config, traffic, e2e, per_layer = harness.load_cell(
            w["name"], MANIFEST)
        assert cell == w
        driver = harness.load_driver(traffic)
        assert all(callable(getattr(driver, f))
                   for f in ("job", "setup", "window"))
        for m in e2e + per_layer:
            assert callable(harness.load_reader(m["name"]))


ARRIVALS = '''"""Open loop: requests due every 1 / rate seconds, each a toy job."""
import time

import numpy as np

from bench import check, graphs


def job(graph, part, seed, timing):
    n, a, b, w = graph
    k = part["k"]
    labels = np.minimum(np.arange(n) // -(-n // k), k - 1)
    block = np.bincount(labels, minlength=k)
    return {"labels": labels, "cut": check.reference_cut(a, b, w, labels),
            "imbalance": check.reference_imbalance(block, n, k),
            "timings": {}, "level_backends": []}


def answer(graph, part, seed, job, arrival, t0):
    start = time.perf_counter() - t0
    out = job(graph, part, seed, False)
    return {"graph": graph, "out": out, "arrival": arrival, "start": start,
            "end": time.perf_counter() - t0}


def setup(config, mix, seed, job, timing):
    n, u, v = graphs.generate(config["graph"], seed)
    graph = (n, *graphs.edge_list(u, v))
    t0 = time.perf_counter()
    return {"graph": graph, "config": config, "mix": mix,
            "warm": [answer(graph, config["partition"], seed, job, 0.0, t0)]}


def window(state, seconds, job, timing, after_job):
    t0, out = time.perf_counter(), []
    gap = 1.0 / state["mix"]["rate_per_s"]
    while len(out) * gap < seconds:
        time.sleep(max(0.0, len(out) * gap - (time.perf_counter() - t0)))
        out.append(answer(state["graph"], state["config"]["partition"], 0,
                          job, len(out) * gap, t0))
        after_job()
    return out
'''


def test_a_new_config_traffic_and_metric_are_files_plus_entries(tmp_path):
    """Each kind is added by new files and manifest entries; nothing that
    is there changes.  A new mix of an existing driver is one data file; a
    new kind of traffic adds a driver, found by name, whose answers carry
    the latencies a reader takes; the harness runs it unchanged."""
    for sub in ("configs", "traffic", "drivers", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, tmp_path / "bench" / sub)
    with open(MANIFEST) as f:
        manifest = json.load(f)
    config = json.loads((ROOT / manifest["configs"][0]["file"]).read_text())
    config["name"] = "rgg2d-n10-k8"
    config["graph"]["n"] = 1024
    config["partition"]["k"] = 8
    (tmp_path / "bench/configs/rgg2d-n10-k8.json").write_text(
        json.dumps(config))
    (tmp_path / "bench/traffic/again.json").write_text(json.dumps(
        {"why": "the one-shot driver under another name", "driver":
         "oneshot"}))
    (tmp_path / "bench/drivers/arrivals.py").write_text(ARRIVALS)
    (tmp_path / "bench/traffic/arrivals.json").write_text(json.dumps(
        {"why": "open loop at a fixed rate", "driver": "arrivals",
         "rate_per_s": 50}))
    (tmp_path / "bench/metrics/latency_max_s.py").write_text(
        "def read(run):\n"
        "    return max(j['end'] - j['arrival'] for j in run['jobs'])\n")
    manifest["configs"].append({**manifest["configs"][0],
                                "name": "rgg2d-n10-k8",
                                "file": "bench/configs/rgg2d-n10-k8.json"})
    for mix in ("again", "arrivals"):
        manifest["workloads"].append({"name": f"rgg2d-n10-k8.{mix}",
                                      "config": "rgg2d-n10-k8",
                                      "traffic": mix, "chips": 1,
                                      "why": "test"})
    manifest["end_to_end"].append({"name": "latency_max_s", "unit": "s",
                                   "better": "lower",
                                   "source": "host_clock",
                                   "workloads": ["rgg2d-n10-k8.arrivals"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    cell, cfg, mix, e2e, per_layer = harness.load_cell(
        "rgg2d-n10-k8.again", tmp_path / "BENCHMARK.json")
    assert cfg["graph"]["n"] == 1024
    assert harness.load_driver(mix, tmp_path).__file__.endswith(
        "drivers/oneshot.py")
    line = harness.run_cell("rgg2d-n10-k8.arrivals", 2**31 + 3, 0.2, False,
                            time.perf_counter(), require_tpu=False,
                            manifest_path=tmp_path / "BENCHMARK.json")
    assert line["correct"] and line["attempted"] == 10
    assert "latency_max_s" in line["metrics"] and "setup_s" in line["metrics"]
    assert list(line)[-1] == "checks"
