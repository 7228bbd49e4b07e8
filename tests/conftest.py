import os
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")
# src for the package; the checkout root for chip_smoke and benchmarks
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

# fixture corpora for the static-analysis suite mirror the repo layout
# (including tests/test_*.py files with planted violations) — they are
# inputs to repro.analysis, never test modules to collect
collect_ignore = ["fixtures"]

import jax
import jax.numpy as jnp
import pytest

from repro.common.config import get_arch
from repro.models.dims import make_dims


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


#: LPDDR4-3200 tRFCab/tRFCpb per die (ns) at 8/16/32 Gb
LPDDR4_TRFC = {8: (180.0, 90.0), 16: (280.0, 140.0), 32: (380.0, 190.0)}


def lpddr4(density: int = 32, **kw):
    """An LPDDR4-3200 `DramTiming` (ticks of tBL = 5 ns) whose per-bank
    refreshes keep the two issue rules, a 90 ns tpbR2pbR spacing and
    refresh rounds; `kw` sets the layout or overrides a value."""
    from repro.core.refresh.timing import DramTiming
    ab, pb = LPDDR4_TRFC[density]
    return DramTiming(**{**dict(
        density_gb=density, tRCD=18.0, tRP=18.0, tCL=17.5, tBL=5.0,
        tWR=18.0, tWTR=10.0, tRTW=18.75, tRTR=1.25, tREFI=3904.0,
        tRFC_ab=ab, tRFC_pb=pb, tpbR2pbR=90.0, refpb_rounds=True), **kw})


def reduced(name: str):
    cfg = get_arch(name).reduced()
    dims = make_dims(cfg, tp=1, param_dtype=jnp.float32,
                     compute_dtype=jnp.float32)
    return cfg, dims
