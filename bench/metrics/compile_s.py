"""Seconds of JAX's ``/jax/core/compile/*`` events during set-up: tracing,
lowering and XLA compilation, or persistent-cache reads."""


def read(run):
    return run["setup_compile_s"]
