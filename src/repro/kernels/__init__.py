# Pallas TPU kernels for the compute hot-spots.
#   gain/  — per-vertex block-connectivity scoreboard (conn/gain/target),
#            the inner loop of Jet move generation, LP and rebalancing.
#   halo/  — halo move application (greedy-rebalance epilogue).
#   flash/ — causal flash attention (LM prefill/training hot-spot).
# Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# wrapper; interpret-mode on CPU) and ref.py (pure-jnp oracle).
import jax


def default_interpret(interpret: bool | None = None) -> bool:
    """The one resolution rule for a kernel call's ``interpret`` flag:
    explicit values win; ``None`` means compiled Mosaic on a TPU backend and
    the Pallas interpreter everywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


