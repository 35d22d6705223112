"""Compile-only checks of the Pallas kernels for a described TPU v5e.

Interpret mode (the rest of the suite) cannot see what Mosaic refuses:
lane slices it cannot prove 128-aligned, scoped VMEM over the 16 MiB
default, gathers across vregs.  These tests compile the kernels ahead of
time for a v5e that is described, not attached, at the corners of the
envelopes that ``resolve_gain``/``resolve_halo`` let them serve (DESIGN.md
§5).  Nothing runs; a pass says the chip's compiler accepts the kernel.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the workers of a
parallel run must all collect the same tests.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gain.kernel import gain_scoreboard_pallas
from repro.kernels.halo.kernel import halo_apply_pallas
from repro.refine.gain import PALLAS_MAX_DEG, PALLAS_MAX_K


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed / describable
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# (n_local, padded degree): a whole (2^20, d) adjacency for d ≤ 64; at the
# degree bound an (n, 2048) adjacency of 2^20 rows would exceed HBM alone
GAIN_SHAPES = [(1 << 20, 16), (1 << 20, 64), (1 << 14, PALLAS_MAX_DEG)]


@pytest.mark.parametrize("k", [128, PALLAS_MAX_K])
@pytest.mark.parametrize("n,d", GAIN_SHAPES)
def test_gain_scoreboard_compiles_for_v5e(one_chip, n, d, k):
    args = (_spec((d, n), jnp.int32, one_chip),
            _spec((d, n), jnp.float32, one_chip),
            _spec((n,), jnp.int32, one_chip),
            _spec((n,), jnp.float32, one_chip),
            _spec((k,), jnp.float32, one_chip))
    _assert_kernel(gain_scoreboard_pallas.lower(
        *args, tile_n=256, deg_chunk=16).compile())


# the finest level of the rgg2d-n17-k16 benchmark cell
CELL_N, CELL_M, CELL_MAX_DEG, CELL_K = 131_072, 1_044_916, 21, 16


def _scoped_ops(hlo: str, scope: str, op: str):
    """Result shapes of the ``op`` instructions of a compiled module whose
    innermost rung scope (``engine.py``'s named scopes) is ``scope``."""
    rungs = ("afterburner", "cut", "gain", "move", "rebalance")
    shapes = []
    for line in hlo.splitlines():
        m = re.search(rf"= (\S+) {op}\(.*op_name=\"([^\"]*)\"", line)
        if m and [p for p in m.group(2).split("/") if p in rungs][-1:] == [
                scope]:
            shapes.append(m.group(1).split("{")[0])
    return shapes


def test_level_afterburner_reads_no_slot_gathers_for_v5e(one_chip):
    """The fused all-gather level program at the benchmark cell's finest
    shape, on the Pallas gain: its afterburner runs in the (D, N) layout,
    so it gathers no m-slot array and scatters no segment sum; its reads
    are (D, N) lookups."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.refine.drivers import _sharded_level_fn

    mesh = Mesh(np.array(list(one_chip.device_set)), ("pe",))
    _, fn = _sharded_level_fn(mesh, CELL_K, CELL_N, CELL_N, 12, 64, "pallas",
                              CELL_MAX_DEG, False, "jet")
    row, rep = NamedSharding(mesh, P("pe", None)), NamedSharding(mesh, P())
    n, m = CELL_N, CELL_M
    args = (_spec((1, m), jnp.int32, row), _spec((1, m), jnp.int32, row),
            _spec((1, m), jnp.float32, row), _spec((1, n), jnp.float32, row),
            _spec((1, n), jnp.bool_, row),
            _spec((1,), jnp.int32, NamedSharding(mesh, P("pe"))),
            _spec((1, n), jnp.int32, row), _spec((2,), jnp.uint32, rep),
            _spec((), jnp.float32, rep), _spec((4,), jnp.float32, rep))
    hlo = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    d = 32  # 21 rounded up to the default degree chunk of 16
    gathers = _scoped_ops(hlo, "afterburner", "gather")
    assert gathers
    assert all(s.endswith(f"[{d},{n}]") for s in gathers), gathers
    assert not _scoped_ops(hlo, "afterburner", "scatter")


def test_halo_apply_compiles_for_v5e(one_chip):
    from repro.kernels.halo import HALO_MAX_CAND

    n, c = 1 << 20, HALO_MAX_CAND
    args = (_spec((n,), jnp.int32, one_chip),
            _spec((n,), jnp.int32, one_chip),
            _spec((c,), jnp.int32, one_chip),
            _spec((c,), jnp.int32, one_chip),
            _spec((c,), jnp.int32, one_chip))
    _assert_kernel(halo_apply_pallas.lower(
        *args, tile_n=256, cand_chunk=128).compile())
