"""The chip entry points refuse to measure off the chip, the compile cache
is placed from outside, and a run-time peak lookup never guesses
(utils/chip.py, chip_smoke.py, bench.py, sim/cost_model.chip_perf and
run_perf)."""

import os
import subprocess
import sys

import jax
import pytest

from flexflow_tpu.utils import chip

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr   # names what it found
    assert '"ok"' not in p.stdout         # and prints no result


def test_bench_refuses_cpu():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        bench.run(model="alexnet", batch_size=8, iters=1, warmup=1)


def test_compile_cache_placed_from_outside(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert chip.compile_cache_dir() is None
    assert chip.enable_compile_cache() == "/somewhere/else"
    assert calls == []                    # the environment alone places it
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert chip.compile_cache_dir() == want
    assert chip.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]


def test_no_other_cache_directory_in_code():
    """utils/chip.py is the one place that names the cache option."""
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d not in ("tests",
                                                          "chiprun_out")]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path) as fh:
                    if "compilation_cache_dir" in fh.read():
                        hits.append(os.path.relpath(path, REPO))
    assert hits == ["flexflow_tpu/utils/chip.py"]


def test_chip_perf_unknown_kind_raises():
    from flexflow_tpu.sim.cost_model import chip_perf

    v5e = chip_perf("TPU v5 lite")
    assert v5e.peak_flops == 1.97e14 and v5e.hbm_bandwidth == 8.19e11
    with pytest.raises(ValueError, match="no peak numbers"):
        chip_perf("cpu")


def test_run_perf_has_no_peaks_off_the_chip():
    """What fit's live mfu gauges and --profiling divide by: the device's
    own peaks on a TPU, nothing on a CPU, an error for an unknown TPU."""
    from types import SimpleNamespace as Dev

    from flexflow_tpu.sim.cost_model import chip_perf, run_perf

    assert run_perf(jax.devices()[0]) is None        # the suite's CPU
    assert run_perf(Dev(platform="tpu", device_kind="TPU v5 lite")) \
        is chip_perf("TPU v5 lite")
    with pytest.raises(ValueError, match="no peak numbers"):
        run_perf(Dev(platform="tpu", device_kind="TPU v9"))
