# Runtime hygiene shared by every repo entry point — check.sh, the CI jobs
# and the bench harnesses all `source` this (olmax / HomebrewNLP-Jax run.sh
# lineage).  Rules:
#
#   * additive only: never overrides a variable the caller already
#     exported;
#   * never sets JAX_ENABLE_X64 — fp64 would break the fp32-exactness
#     determinism contract (DESIGN.md §3);
#   * every knob is guarded: a machine without tcmalloc gets a no-op, not
#     a broken interpreter;
#   * no XLA_FLAGS of its own: the installed jaxlib parses XLA_FLAGS once
#     for every backend and hard-aborts on a flag it does not know (the
#     TPU step-marker flag among them), and TPU compiler flags belong in
#     LIBTPU_INIT_ARGS, which this file never touches.

# faster malloc where the machine ships it; skipped silently otherwise
if [ -z "${LD_PRELOAD:-}" ]; then
  for _repro_tcmalloc in \
      /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
      /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
      /usr/lib/libtcmalloc.so.4; do
    if [ -f "$_repro_tcmalloc" ]; then
      export LD_PRELOAD="$_repro_tcmalloc"
      # silence tcmalloc's large-alloc warnings for graph-sized buffers
      export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-60000000000}"
      break
    fi
  done
  unset _repro_tcmalloc
fi

# quiet TF/XLA C++ logging (dataset + compilation chatter)
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"
