"""The closed body's ring bank queues: each slot's write flag rides in the
low bit of the int32 `qc` plane (``core << 1 | is_write``), so the queues
are int32 planes only and an append scatters no bool plane.

The jax backend stays bit-identical to the batched numpy backend at the
paper's 2 channel x 2 rank layout and at the DDR5 bank-group layout that
`tests/test_tpu_aot.py` compiles for the chip, on a scenario whose 8 cores
issue reads and writes; the final `qc` plane shows that every core id was
packed with both head kinds.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.sweep import CellResult, SweepSpec, sweep, jaxbody
from repro.core.sweep.engine import _Grid, _jax_arbiter
from test_tpu_aot import GROUPS_GRID

REQS, SEED = 96, 1
POLICIES = ("ideal", "ref_ab", "ref_pb", "darp", "sarp_pb", "dsarp")
LAYOUTS = {
    "2ch2r": SweepSpec(policies=POLICIES, scenarios=("closed_multirank",),
                       densities=(32,), reqs=REQS, seed=SEED, mode="closed",
                       n_channels=2, n_ranks=2),
    "groups": dataclasses.replace(GROUPS_GRID, reqs=REQS, seed=SEED),
}
RING_PLANES = ("qa", "qr", "qs", "qc")


@pytest.mark.parametrize("layout", LAYOUTS)
def test_closed_ring_queue_packs_core_and_write_flag(layout):
    spec = LAYOUTS[layout]
    cfg, cst, s0 = jaxbody.program(_Grid(spec))
    ring = s0["qc"].shape[0]
    assert "qw" not in s0
    assert all(s0[k].dtype == np.int32 for k in RING_PLANES)
    assert not [k for k, v in s0.items()
                if v.dtype == bool and v.shape == (ring,)]

    out = jaxbody.run_loop(cfg, cst, _jax_arbiter("jnp"), s0)
    packed = set(np.unique(np.asarray(out["qc"])).tolist())
    assert {c << 1 | w for c in range(cfg.C) for w in (0, 1)} <= packed

    jax_res, batched = sweep(spec, "jax"), sweep(spec, "batched")
    bad = [(x.policy, x.density_gb, f)
           for x, y in zip(jax_res.cells, batched.cells)
           for f in CellResult.__dataclass_fields__
           if getattr(x, f) != getattr(y, f)]
    assert not bad, bad[:8]
    assert sum(c.writes_done for c in batched.cells) > 0
