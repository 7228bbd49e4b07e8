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


def reduced(name: str):
    cfg = get_arch(name).reduced()
    dims = make_dims(cfg, tp=1, param_dtype=jnp.float32,
                     compute_dtype=jnp.float32)
    return cfg, dims
