"""`chip_smoke.py`'s phases on the CPU at reduced size, its refusal to run
without a TPU, and the compile-cache helper the entry points call."""
import os
import shutil
import subprocess
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

import chip_smoke
from repro.common import compile_cache
from repro.common.config import get_arch
from repro.core.sweep import SweepSpec

SMALL = {
    "closed": SweepSpec(policies=("ideal", "ref_ab", "dsarp"),
                        scenarios=("closed_mixed", "closed_low_mlp"),
                        densities=(8, 32), reqs=64, seed=0, mode="closed"),
    "open": SweepSpec(policies=("ideal", "ref_pb", "darp"),
                      scenarios=("mixed", "bank_camping"),
                      densities=(8, 32), reqs=64, seed=0),
}


@pytest.mark.parametrize("mode", ["closed", "open"])
def test_sweep_phase_small_grid(mode):
    out = chip_smoke.sweep_phase(SMALL[mode])
    assert out["bit_identical"] is True
    assert out["cells"] == 12
    assert out["cold_s"] > 0 and out["warm_s"] > 0


def test_serve_phase_reduced_model():
    out = chip_smoke.serve_phase(get_arch("qwen2-0.5b").reduced(),
                                 n_requests=2)
    assert out["requests"] == 2 and out["new_tokens"] == 8
    # one decode step per round until both requests have 8 tokens
    assert out["decode_steps"] >= 2 * 8


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Copied into a directory that holds nothing else of the repo, the
    script exits non-zero and prints no result."""
    shutil.copy(chip_smoke.__file__, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_honours_environment(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV, "/cache/from/env")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/cache/from/env"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fallback_is_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    expected = os.path.join(root, ".jax_cache")
    assert compile_cache.DEFAULT_DIR == expected
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.use_compile_cache() == expected
        assert jax.config.jax_compilation_cache_dir == expected
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
