#!/usr/bin/env bash
# Tier-1 gate: the full test suite plus a 4-device smoke of the distributed
# V-cycle (sharded coarsening end-to-end under shard_map).
#
# --batch: the request-batched engine preflight instead (CI's batch-smoke
# leg): the batched smoke sweep — bench.py checks the schema and the
# one-dispatch-per-level-per-batch contract per cell — plus the
# B=1-equivalence / batch-invariance suite and the bench-harness tests.
#
# --serve: the request-stream serving preflight (CI's serve-smoke leg):
# the serve smoke bench — serve_bench.py checks bit-identity vs the
# per-request baseline for BOTH fronts (sync partition_stream and the
# async PartitionService in replay mode), the steady-state zero-retrace /
# zero-alloc contract and the schema per cell — plus the serving test
# suite (scheduler determinism, buffer-pool counters, stream bit-identity,
# PartitionConfig facade identity, service lifecycle/degradation).
#
# --ckpt: the crash/fault-injection preflight (CI's ckpt-smoke leg): the
# out-of-core ingest + checkpoint-store + resumable-V-cycle suites, plus —
# via REPRO_CKPT_SUBPROC=1 — the kill-and-resume subprocess cells that
# SIGKILL the CLI mid-V-cycle (same-P and elastic P=8↔P=1) and are too
# heavy for tier-1.
set -euo pipefail
cd "$(dirname "$0")/.."

# shared runtime hygiene (tcmalloc, TF log level)
source scripts/run_env.sh

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${1:-}" == "--batch" ]]; then
  echo "== batched-engine preflight =="
  PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python benchmarks/bench.py --smoke --batch 4 \
    --out "${BENCH_BATCH_OUT:-/tmp/BENCH_batch_smoke.json}"
  python -m pytest -x -q tests/test_batch_parity.py tests/test_bench.py
  echo "check.sh --batch: all green"
  exit 0
fi

if [[ "${1:-}" == "--ckpt" ]]; then
  echo "== out-of-core ingest + resumable-V-cycle preflight =="
  REPRO_CKPT_SUBPROC=1 JAX_PLATFORMS=cpu \
    python -m pytest -x -q tests/test_ingest.py tests/test_checkpoint.py \
    tests/test_ckpt_faults.py tests/test_vcycle_ckpt.py \
    tests/test_kill_resume.py
  echo "check.sh --ckpt: all green"
  exit 0
fi

if [[ "${1:-}" == "--serve" ]]; then
  echo "== request-stream serving preflight =="
  PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python benchmarks/serve_bench.py --smoke \
    --out "${SERVE_BENCH_OUT:-/tmp/SERVE_smoke.json}"
  python -m pytest -x -q tests/test_serve.py tests/test_service.py \
    tests/test_config.py tests/test_bench.py
  echo "check.sh --serve: all green"
  exit 0
fi

# Version echo first: when a matrix leg (e.g. the latest-jax canary) breaks,
# the log says immediately which toolchain it broke under.
echo "== versions =="
python - <<'PY'
import sys
import jax
import numpy
import pytest
print(f"python {sys.version.split()[0]}")
print(f"jax {jax.__version__}")
print(f"numpy {numpy.__version__}")
print(f"pytest {pytest.__version__}")
PY

# Collection preflight: surface import-time breakage (a broken module, a bad
# test import) as an immediate failure instead of mid-matrix; pytest exits
# non-zero on any collection error, which set -e turns fatal.  The (long)
# collected-test listing is suppressed, but the ERRORS section is replayed
# on failure so the import traceback reaches the log.
echo "== pytest collection preflight =="
collect_log="$(mktemp)"
python -m pytest --co -q >"$collect_log" 2>&1 \
  || { cat "$collect_log"; rm -f "$collect_log"; exit 1; }
rm -f "$collect_log"

python -m pytest -x -q

echo "== 4-device distributed V-cycle smoke =="
# the identical entry point CI runs — see src/repro/launch/smoke.py
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
python -m repro.launch.smoke
echo "check.sh: all green"
