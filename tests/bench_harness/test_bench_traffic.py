"""The benchmark's traffic: the same seed gives the same demand, demand
validates, and every seed gives the same padded shapes, so no seed
compiles anew."""
import json
import os

import numpy as np
import pytest
from _bench_helpers import REPO

from bench import harness
from bench.traffic import build

CELLS = {"fig_closed": "ddr3-1333-1ch1r", "open_grid": "ddr3-1333-1ch1r",
         "dse_closed": "ddr3-1333-2ch2r"}
SEEDS = (0, 17, 2 ** 31 + 5)


def _load(kind, name):
    with open(os.path.join(REPO, "bench", kind, name + ".json")) as f:
        return json.load(f)


def _mix(traffic):
    mix = _load("workloads", traffic)
    if traffic == "dse_closed":     # its 100 mixes share one generator
        mix["scenarios"] = [dict(mix["scenarios"][0], mixes=4)]
    return mix, _load("configs", CELLS[traffic])


def _built(mix, config, seed):
    return build(mix, config, seed, harness.load_reference(config))


def _arrays(scn):
    return {k: v for k, v in vars(scn).items() if isinstance(v, np.ndarray)}


def _padded_shape(tr):
    """What fixes the program's compiled shapes: per-core stream shapes
    and MLP (closed), the longest per-bank queue (open)."""
    if tr.mode == "closed":
        return [(s.is_write.shape, s.mlp) for s in tr.scenarios]
    return max(int(np.bincount(s.bank, minlength=s.n_banks).max())
               for s in tr.scenarios)


@pytest.mark.parametrize("traffic", sorted(CELLS))
def test_same_seed_same_demand(traffic):
    mix, config = _mix(traffic)
    a, b = _built(mix, config, 17), _built(mix, config, 17)
    c = _built(mix, config, 18)
    for x, y in zip(a.scenarios, b.scenarios):
        assert all((u == v).all() for u, v in zip(_arrays(x).values(),
                                                  _arrays(y).values()))
    assert any((u != v).any() for x, z in zip(a.scenarios, c.scenarios)
               for u, v in zip(_arrays(x).values(), _arrays(z).values()))


@pytest.mark.parametrize("traffic", sorted(CELLS))
def test_demand_validates(traffic):
    mix, config = _mix(traffic)
    tr = _built(mix, config, 2 ** 31 + 5)
    for s in tr.scenarios:
        s.validate()
    lay = config["layout"]
    assert {s.n_banks for s in tr.scenarios} == {
        lay["n_channels"] * lay["n_ranks"] * lay["n_banks"]}
    assert len(tr.cells()) == len(tr.policies) * len(tr.scenarios) * 3


@pytest.mark.parametrize("traffic", sorted(CELLS))
def test_every_seed_same_padded_shapes(traffic):
    mix, config = _mix(traffic)
    shapes = {json.dumps(_padded_shape(_built(mix, config, s)), default=str)
              for s in SEEDS}
    assert len(shapes) == 1


def test_open_seed_relabels_banks_keeping_subarrays():
    mix, config = _mix("open_grid")
    a, b = _built(mix, config, 1), _built(mix, config, 2)
    for x, y in zip(a.scenarios, b.scenarios):
        assert (x.arrive == y.arrive).all()
        assert (x.sub == y.sub).all() and (x.row % 8 == x.sub).all()
        assert sorted(np.bincount(x.bank, minlength=8)) == sorted(
            np.bincount(y.bank, minlength=8))
