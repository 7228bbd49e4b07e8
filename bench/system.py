"""The system under test, as the benchmark drives it: the sweep engine's
device path. Everything this benchmark takes from the program goes
through this module.

`device_sweep` is the one call of the device path. The window calls it
back to back on the one spec `make_spec` builds in set-up.
"""
from __future__ import annotations

import os

import jax

from repro.common.compile_cache import ENV, use_compile_cache
from repro.core.refresh.scenarios import ClosedDemand, Trace
from repro.core.refresh.workload import Workload
from repro.core.sweep import SweepSpec, sweep

__all__ = ["compile_cache", "make_spec", "device_sweep"]

#: the benchmark's compile cache: a fixed directory inside the checkout
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def compile_cache() -> str:
    """Turn JAX's persistent compilation cache on in `CACHE_DIR`, whatever
    the environment names, and hand the program that directory; cache
    every program however short its compile, so that a run after the
    first in a checkout compiles nothing."""
    os.environ[ENV] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return use_compile_cache()


def _program_scenario(scn, dt_ns: float):
    """A benchmark scenario as the program's input type."""
    if hasattr(scn, "arrive"):
        return Trace(scn.name, scn.arrive, scn.bank, scn.row, scn.sub,
                     scn.is_write, scn.n_banks, scn.n_subarrays).validate()
    p = scn.params
    wl = Workload(name=scn.name, n_cores=p["n_cores"], mlp=p["mlp"],
                  think_ns=p["think_ns"], row_hit_rate=p["row_hit_rate"],
                  write_ratio=p["write_ratio"],
                  reqs_per_core=int(scn.is_write.shape[1]), seed=scn.seed)
    return ClosedDemand(scn.name, wl, scn.is_write, scn.bank, scn.row,
                        scn.sub, scn.think, scn.n_banks, scn.n_subarrays,
                        dt_ns).validate()


def make_spec(traffic, config: dict) -> SweepSpec:
    """The grid of `traffic` on `config`'s DRAM layout."""
    lay, wb = config["layout"], config["wbuf"]
    return SweepSpec(
        policies=traffic.policies,
        scenarios=[_program_scenario(s, config["dt_ns"])
                   for s in traffic.scenarios],
        densities=traffic.densities, reqs=traffic.reqs,
        dt_ns=config["dt_ns"], n_banks=lay["n_banks"],
        n_subarrays=lay["n_subarrays"], n_ranks=lay["n_ranks"],
        n_channels=lay["n_channels"], wbuf_hi=wb["hi"], wbuf_lo=wb["lo"],
        wbuf_cap=wb["cap"], mode=traffic.mode)


def device_sweep(spec: SweepSpec) -> list:
    """One sweep on the device path: the grid's `CellResult`s, in grid
    order, on the host."""
    return sweep(spec, backend="jax").cells
