"""Ahead-of-time compiles for one TPU v5e chip, described and not attached.

The TPU compiler is installed with JAX and compiles for a described
`v5e:2x2` topology, so these tests catch what interpret mode cannot: a
program or kernel that the chip's compiler refuses. Nothing runs. They
cover the sweep's device path (the `jax` backend's while loop at the
paper's closed grid and the open-loop grid that `chip_smoke.py` runs), the
Pallas arbiter kernel, and the megakernel, which Mosaic still refuses.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and this
file's worker keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from chip_smoke import CLOSED_GRID, OPEN_GRID
from repro.core.sweep import jaxbody
from repro.core.sweep.engine import _Grid, _jax_arbiter

GRIDS = {"closed": CLOSED_GRID, "open": OPEN_GRID}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e host, with the persistent compilation
    cache off (a program compiled for an absent chip cannot be read back
    from it)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shapes of `tree`'s arrays, placed on `sharding`."""
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        np.shape(a), jnp.asarray(a).dtype, sharding=sharding), tree)


@pytest.mark.parametrize("mode", ["closed", "open"])
def test_jax_backend_loop_compiles_for_v5e(one_chip, mode):
    """The program `sweep(..., backend="jax")` runs, at the grid
    `chip_smoke.py` runs it on."""
    cfg, cst, s0 = jaxbody.program(_Grid(GRIDS[mode]))
    compiled = jaxbody.run_loop.lower(
        cfg, _on(one_chip, cst), _jax_arbiter("jnp"),
        _on(one_chip, s0)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9


@pytest.mark.parametrize("mode", ["closed", "open"])
def test_sweep_arbiter_kernel_compiles_for_v5e(one_chip, mode):
    """The Pallas arbiter with `interpret=False`, at each grid's [G, B]
    score plane. `arbiter="pallas"` picks interpret mode on the CPU, so
    the kernel is lowered directly."""
    from repro.kernels.sweep_arbiter import _arbiter_call

    grid = _Grid(GRIDS[mode])
    gb = jax.ShapeDtypeStruct((grid.G, grid.B), jnp.int32, sharding=one_chip)
    g1 = jax.ShapeDtypeStruct((grid.G,), jnp.int32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    occ = gb if grid.closed else None
    compiled = _arbiter_call.lower(
        t, *([gb] * 8), g1, gb, occ, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(strict=True, raises=(AssertionError, ValueError),
                   reason="Mosaic refuses the megakernel "
                          "(sweep_megakernel.REFUSAL)")
@pytest.mark.parametrize("mode", ["closed", "open"])
def test_megakernel_compiles_for_v5e(one_chip, mode):
    """The fused megakernel with `interpret=False`. While Mosaic refuses
    it, `run_mega` raises `MegakernelRefused` instead of lowering it;
    the day this compiles, the strict xfail fails and the refusal must
    be lifted."""
    from repro.kernels import sweep_megakernel as mk

    grid = _Grid(GRIDS[mode], stack_streams=False)
    _, tile_scn, tile = mk._layout(grid, None)
    if grid.closed:
        cfg = jaxbody.closed_cfg(grid)
        streams = (grid.scn_write, grid.scn_bank, grid.scn_row,
                   grid.scn_sub, grid.scn_think, grid.scn_nreq)
        call = mk._closed_call_jit
    else:
        cfg = jaxbody.open_cfg(grid)
        streams = (grid.scn_qa, grid.scn_qr, grid.scn_qs, grid.scn_qw,
                   grid.scn_npb)
        call = mk._open_call_jit
    n_tiles = len(tile_scn)
    args = [np.zeros(n_tiles, np.int32),
            np.zeros((n_tiles * tile, mk.MEGA_NPARAM), np.int32),
            *(np.asarray(a, np.int32) for a in streams)]
    call.lower(*_on(one_chip, args), cfg=cfg, n_tiles=n_tiles, tile=tile,
               interpret=False).compile()
