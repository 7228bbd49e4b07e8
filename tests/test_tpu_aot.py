"""Ahead-of-time compiles for one TPU v5e chip, described and not attached.

The TPU compiler is installed with JAX and compiles for a described
`v5e:2x2` topology, so these tests catch what interpret mode cannot: a
program or kernel that the chip's compiler refuses. Nothing runs. They
cover the sweep's device path (the `jax` backend's while loop at the
paper's closed grid and the open-loop grid that `chip_smoke.py` runs,
at a DDR5 layout with bank groups and same-bank refresh, and at an
LPDDR4 layout whose per-bank refreshes keep tpbR2pbR and refresh
rounds).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and this
file's worker keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import lpddr4
from jax.sharding import SingleDeviceSharding

from chip_smoke import CLOSED_GRID, OPEN_GRID
from repro.core.refresh.timing import DramTiming
from repro.core.sweep import SweepSpec, jaxbody
from repro.core.sweep.engine import _Grid, _jax_arbiter

#: 2 subchannels x 2 ranks x 8 bank groups x 4 banks, DDR5-4800-like
#: timing on ticks of tBL = 10/3 ns
_DDR5 = dict(n_channels=2, n_ranks=2, n_banks=32, n_bank_groups=8)
GROUPS_GRID = SweepSpec(
    policies=("ref_ab", "ref_pb", "darp", "sarp_pb", "dsarp", "ideal"),
    scenarios=("closed_multirank",), densities=(32,), reqs=320, seed=1,
    mode="closed", dt_ns=10 / 3, **_DDR5,
    timing={32: DramTiming(
        density_gb=32, tRCD=16.25, tRP=16.25, tCL=16.67, tBL=10 / 3, tWR=30.0,
        tWTR=10.0, tCCD_L=5.0, tCCD_S=10 / 3, tREFI=1950.0,
        tRFC_ab=220.0, tRFC_pb=190.0, **_DDR5)})
#: 4 x16 channels x 2 ranks x 8 banks of LPDDR4-3200, ticks of 5 ns
_LPDDR4 = dict(n_channels=4, n_ranks=2)
LPDDR4_GRID = SweepSpec(
    policies=("ref_ab", "ref_pb", "darp", "sarp_pb", "dsarp", "ideal"),
    scenarios=("closed_multirank",), densities=(32,), reqs=320, seed=1,
    mode="closed", dt_ns=5.0, **_LPDDR4,
    timing={32: lpddr4(32, **_LPDDR4)})
GRIDS = {"closed": CLOSED_GRID, "open": OPEN_GRID, "groups": GROUPS_GRID,
         "lpddr4": LPDDR4_GRID}


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


@pytest.mark.parametrize("mode", ["closed", "open", "groups", "lpddr4"])
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
    # the tick-phase scopes survive the chip's compiler in op_name
    hlo = compiled.as_text()
    for scope in ("tick.front_end", "tick.refresh", "tick.arbitrate",
                  "tick.serve"):
        assert f"/{scope}/" in hlo, scope
    # each bank's queue head and head subarray are one-hot lane selects
    # (`jaxbody.pick`): the chip's compiler serialises a gather's indices
    arb_gathers = [ln for ln in hlo.splitlines()
                   if " gather(" in ln and "/tick.arbitrate/" in ln]
    assert not arb_gathers, arb_gathers[:3]
    if mode != "open":
        # the ring-queue appends scatter int32 planes only: a slot's
        # write flag rides in `qc`, since a bool plane's packed layout
        # makes its scatter several times slower on the chip
        appends = [ln for ln in hlo.splitlines()
                   if " scatter(" in ln and "/tick.front_end/" in ln]
        assert appends
        pred_appends = [ln for ln in appends if "= pred[" in ln]
        assert not pred_appends, pred_appends[:3]
        # each core's next request (from the packed stream record, `sr`
        # and `sth`), its place among the appends and its bank's tail
        # are one-hot lane selects too
        front_gathers = [ln for ln in hlo.splitlines()
                         if " gather(" in ln and "/tick.front_end/" in ln]
        assert not front_gathers, front_gathers[:3]
