"""Test config: force an 8-device virtual CPU mesh so multi-chip sharding is
exercised without TPU hardware (SURVEY.md §4: the stand-in for the
reference's ability to test multi-node via DISABLE_COMPUTATION + the
simulator).  Must run before jax initializes a backend.  The platform is
pinned through jax.config as well as the tier-1 command's JAX_PLATFORMS=cpu,
so that a bare ``pytest`` on a machine with a chip never takes the chip."""

import contextlib
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")

import pytest

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "flexflow_tpu", "native")
_native_state = {}


def _native_available() -> bool:
    """libffsim.so present, building it once with the in-tree Makefile if
    missing — so CI and fresh clones exercise the native path instead of
    silently skipping.  False (skip, not error) when the toolchain is
    absent."""
    if "ok" not in _native_state:
        lib = os.path.join(_NATIVE_DIR, "libffsim.so")
        if not os.path.exists(lib):
            import subprocess

            try:
                subprocess.run(["make", "-C", _NATIVE_DIR, "libffsim.so"],
                               check=True, capture_output=True)
            except Exception:
                pass
        _native_state["ok"] = os.path.exists(lib)
    return _native_state["ok"]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "native: needs libffsim.so (built from the in-tree C++ toolchain)")


def pytest_collection_modifyitems(config, items):
    if _native_available():
        return
    skip = pytest.mark.skip(
        reason="native toolchain unavailable (libffsim.so missing and "
               "`make -C flexflow_tpu/native` failed)")
    for item in items:
        if "native" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def machine8():
    from flexflow_tpu.machine import MachineModel

    assert jax.device_count() == 8
    return MachineModel()


@pytest.fixture(scope="session")
def machine1():
    from flexflow_tpu.machine import MachineModel

    return MachineModel(devices=jax.devices()[:1])


@pytest.fixture
def pallas_kernels():
    """``with pallas_kernels():`` opens the one kernel gate
    (``ops/pallas.flash_enabled``, which follows the backend and so is
    shut on this CPU mesh): inside the block the flash and fused-CE
    kernels run, in interpret mode.  The callers look the gate up when
    an operator is traced, so a model built inside the block takes the
    kernels and one built outside keeps XLA's paths."""
    from flexflow_tpu.ops import pallas

    @contextlib.contextmanager
    def opened():
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(pallas, "flash_enabled", lambda: True)
            yield

    return opened
