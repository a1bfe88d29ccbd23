"""Pallas TPU kernels for the hot ops.

These are the hand-scheduled compute paths of the framework (the analog of
the reference's hand-written CUDA kernels, e.g. nmt/embed.cu's gather /
scatter-add and the cuDNN leaf tasks): XLA fuses most elementwise work into
the MXU matmuls on its own, so Pallas is reserved for the ops where manual
VMEM tiling beats the compiler — attention's O(S^2) score matrix, which a
flash kernel never materializes in HBM (on a v5e the flash kernels are the
one family here that beats XLA at every shape tried; the fused CE head
saves memory, not time, and the pool and bn_act kernels lose alone —
PERF.md section 6, PR 21 and PR 27).

Kernels run compiled (Mosaic) on TPU and in interpreter mode elsewhere, so
the same code path is exercised by the CPU test suite.

Routing policy (round 13): one ``--pallas auto|on|off`` switch
(:func:`set_policy`, wired from FFConfig by FFModel) replaces ad-hoc
per-kernel defaults.  ``auto`` routes a kernel only when its
``supported()`` gate holds AND its HBM cost model predicts a win on the
concrete geometry (e.g. maxpool.roofline_predicted_win_ms); ``on``
forces every supported kernel; ``off`` keeps the stock XLA paths.  The
per-kernel env vars (``FLEXFLOW_TPU_{FLASH,MAXPOOL,AVGPOOL,BNRELU}``
= 0/1) still override the policy for that one kernel — the test suite's
and single-experiment escape hatch.
"""

import os

from flexflow_tpu.ops.pallas.flash_attention import \
    KEPT_RESULTS as _FLASH_RESULTS, flash_attention

_POLICY = "auto"

# Results the kernels name (``jax.ad_checkpoint.checkpoint_name``) because
# they are dearer to make again than to hold: a block that a model class
# recomputes in the backward pass keeps these and nothing else
# (FFModel._run_recomputed).  A kernel joins by naming its results in its
# own module and adding them here.
KEPT_RESULTS = (*_FLASH_RESULTS,)


def set_policy(policy: str) -> None:
    """Install the process-wide kernel routing policy (FFConfig.pallas).
    Validates eagerly — a typo'd policy fails at model construction, not
    silently at the first pool."""
    global _POLICY
    if policy not in ("auto", "on", "off"):
        raise ValueError(f"pallas policy must be auto|on|off, "
                         f"got {policy!r}")
    _POLICY = policy


def get_policy() -> str:
    return _POLICY


def _env_gate(name: str):
    """Tri-state per-kernel env override: True / False / None (defer to
    the policy)."""
    v = os.environ.get(name, "").lower()
    if v in ("0", "false"):
        return False
    if v in ("1", "true"):
        return True
    return None


def flash_enabled() -> bool:
    """Policy gate for the flash kernel: under ``auto``, on on TPU
    (compiled via Mosaic; on a v5e, forward + backward at the GPT-2
    cell's shape, b16 h12 s1024 d64 causal bf16, took 3.15 ms against
    14.14 for XLA's blockwise attention, and 3.03 against 15.33 at b1 h4
    s8192 — tools/chip_kernels.py, host clock; PERF.md section 6,
    PR 27), off elsewhere (interpret mode is for tests, too slow for
    training).  FLEXFLOW_TPU_FLASH=0/1 overrides."""
    env = _env_gate("FLEXFLOW_TPU_FLASH")
    if env is not None:
        return env
    if _POLICY != "auto":
        return _POLICY == "on"
    import jax

    return jax.default_backend() == "tpu"


def maxpool_enabled() -> bool:
    """Candidacy gate for the Pallas max-pool backward.  Per-op it beats
    XLA's select_and_scatter ~2x (2.9 vs 5.0 ms on Inception's two big
    pools, compiled-step profile), but end-to-end the swap measures
    inside the run-to-run jitter band or slightly negative (1926-1942 vs
    1946 img/s across three full designs, round 4): the forward sel
    plane costs a second pass over x that XLA's fused reduce_window
    pipeline never pays.  Under ``auto`` the kernel is therefore only a
    CANDIDATE on TPU — Pool2D._use_pallas makes the final call with
    maxpool.roofline_predicted_win_ms on the concrete geometry, which
    prices that sel pass honestly.  ``on`` / FLEXFLOW_TPU_MAXPOOL=1
    force every supported geometry (the measurement escape)."""
    env = _env_gate("FLEXFLOW_TPU_MAXPOOL")
    if env is not None:
        return env
    if _POLICY != "auto":
        return _POLICY == "on"
    import jax

    return jax.default_backend() == "tpu"


def maxpool_cost_gated() -> bool:
    """True when the routing decision should consult the per-geometry
    cost model (policy ``auto`` with no env override); forced modes
    route every supported geometry unconditionally."""
    return _env_gate("FLEXFLOW_TPU_MAXPOOL") is None and _POLICY == "auto"


def avgpool_enabled() -> bool:
    """Policy gate for the Pallas avg-pool backward (ops/pallas/avgpool
    .py — non-overlapping/global geometries only).  No measured or
    modeled win yet (the maxpool experience — per-op 2x, end-to-end
    jitter-band — sets the evidence bar), so ``auto`` keeps it OFF;
    ``on`` / FLEXFLOW_TPU_AVGPOOL=1 force it."""
    env = _env_gate("FLEXFLOW_TPU_AVGPOOL")
    if env is not None:
        return env
    return _POLICY == "on"


def bnrelu_enabled() -> bool:
    """Policy gate for the fused batchnorm-normalize+ReLU kernel pair
    (ops/pallas/bn_act.py): same pending-measurement status as
    avgpool_enabled — ``auto`` keeps it off, ``on`` /
    FLEXFLOW_TPU_BNRELU=1 force it."""
    env = _env_gate("FLEXFLOW_TPU_BNRELU")
    if env is not None:
        return env
    return _POLICY == "on"


__all__ = ["KEPT_RESULTS", "avgpool_enabled", "bnrelu_enabled",
           "flash_attention", "flash_enabled", "get_policy",
           "maxpool_cost_gated", "maxpool_enabled", "set_policy"]
