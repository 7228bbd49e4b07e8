"""The system under test, as the benchmark drives it: the sweep engine's
device path. Everything this benchmark takes from the program goes
through this module.

`device_sweep` is the one call of the device path. The window calls it
back to back on the one spec `make_spec` builds in set-up.

A configuration file reaches the program whole: its layout goes into
`SweepSpec` key by key, and its timing, at each density of the grid,
into the program's `DramTiming`. A key the program's types do not know
raises at set-up, so nothing the file states is dropped on the way.
"""
from __future__ import annotations

import dataclasses
import os

import jax

from repro.common.compile_cache import ENV, use_compile_cache
from repro.core.refresh.scenarios import ClosedDemand, Trace
from repro.core.refresh.timing import DramTiming, timing_for_density
from repro.core.refresh.workload import Workload
from repro.core.sweep import SweepSpec, sweep

__all__ = ["compile_cache", "dram_timing", "takes_timing", "make_spec",
           "device_sweep"]

#: the `SweepSpec` field that takes a grid's timing, ``{density_gb:
#: DramTiming}``, where the program has it; without it the program
#: simulates its own table (`timing_for_density`)
TIMING_FIELD = "timing"

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


def dram_timing(config: dict, density: int) -> DramTiming:
    """`config`'s DRAM at `density` Gb as the program's `DramTiming`: its
    layout and every key of its `timing_ns`, with ``tRFC_ab_pb`` at that
    density as ``tRFC_ab`` and ``tRFC_pb``. A key `DramTiming` does not
    know, or a density the file gives no tRFC, raises."""
    tm = dict(config["timing_ns"])
    ab, pb = tm.pop("tRFC_ab_pb")[str(density)]
    return DramTiming(density_gb=density, tRFC_ab=ab, tRFC_pb=pb,
                      **config["layout"], **tm)


def takes_timing() -> bool:
    """Whether `SweepSpec` takes a grid's timing (`TIMING_FIELD`)."""
    return TIMING_FIELD in {f.name for f in dataclasses.fields(SweepSpec)}


def make_spec(traffic, config: dict) -> SweepSpec:
    """The grid of `traffic` on `config`'s DRAM: its layout, its timing at
    each density of the grid, `dt_ns` and its write buffer.

    Where `SweepSpec` takes no timing, a timing other than the program's
    own table raises: the program would simulate another DRAM than the
    one the reference is given."""
    lay, wb = config["layout"], config["wbuf"]
    timing = {d: dram_timing(config, d) for d in traffic.densities}
    given = {}
    if takes_timing():
        given[TIMING_FIELD] = timing
    else:
        for d, T in timing.items():
            if T != timing_for_density(d, **lay):
                raise ValueError(
                    f"{config['name']}: the DRAM timing at {d} Gb differs "
                    f"from the program's own table, and SweepSpec takes no "
                    f"{TIMING_FIELD!r}; the program cannot simulate it")
    return SweepSpec(
        policies=traffic.policies,
        scenarios=[_program_scenario(s, config["dt_ns"])
                   for s in traffic.scenarios],
        densities=traffic.densities, reqs=traffic.reqs,
        dt_ns=config["dt_ns"], wbuf_hi=wb["hi"], wbuf_lo=wb["lo"],
        wbuf_cap=wb["cap"], mode=traffic.mode, **lay, **given)


def device_sweep(spec: SweepSpec) -> list:
    """One sweep on the device path: the grid's `CellResult`s, in grid
    order, on the host."""
    return sweep(spec, backend="jax").cells
