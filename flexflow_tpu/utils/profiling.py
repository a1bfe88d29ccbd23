"""Profiling / tracing subsystem.

Reference parity (SURVEY.md §5 "Tracing / profiling"):

  * per-op flag-gated timing — the reference brackets each leaf task with
    cudaEvents when ``profiling`` is set and prints per-op ms
    (conv_2d.cu:514-545, linear.cu:380-385, nmt/lstm.cu:219).  Under XLA the
    whole training step is ONE fused program, so per-op times inside it are
    not observable from the host; the TPU-native equivalent is
    :class:`OpProfiler`, which times each op's real jitted fwd+bwd at its
    shard-local shapes (same harness the simulator's MeasuredCostModel uses,
    itself the analog of scripts/cnn.h measure_*_time) and prints a table.
  * wall-clock via execution fence + Realm clock (cnn.cc:113-128) —
    ``FFModel.fit``'s timed loop.
  * Legion ``-lg:prof`` task-level tracing — :func:`trace`, a context
    manager around ``jax.profiler`` producing TensorBoard/XProf traces of
    the actual compiled program (the authoritative per-fusion timeline).

TPU-native addition: :func:`compiled_cost` pulls FLOPs / bytes-accessed
from XLA's cost analysis of the *compiled* step, giving a roofline summary
that no isolated per-op timing can (XLA fuses across ops).
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
from typing import Dict, List, Optional


class StepClock:
    """Host-side per-step wall clock for the obs subsystem (model.fit).

    ``tick()`` appends one ``perf_counter`` delta per step — no device
    syncs, so the timed loop's async dispatch is unperturbed; under jit
    donation the host timestamps track device step time after the first
    couple of iterations (step N+1's dispatch blocks on N's buffers).
    The deltas are read AFTER the loop, when per-step records are
    written."""

    def __init__(self):
        import time as _time

        self._clock = _time.perf_counter
        self._last = self._clock()
        self.deltas: List[float] = []

    def reset(self):
        self._last = self._clock()

    def tick(self) -> None:
        now = self._clock()
        self.deltas.append(now - self._last)
        self._last = now


@contextlib.contextmanager
def trace(logdir: str):
    """XProf/TensorBoard trace of everything executed inside the block
    (Legion -lg:prof analog).  View with tensorboard --logdir=<dir>."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def time_op_shard(op, pc, dtype: str = "float32",
                  repeats: int = 3) -> Optional[float]:
    """Wall seconds of ONE shard's jitted fwd+grad for ``op`` under
    ``pc`` (shard-local shapes via ``local_clone``), min over
    ``repeats`` timed calls after a warm-up — the measured side of the
    obs ``op_time`` records (fit's sampled op-timing mode) and of the
    drift-attribution join in obs/trace.py.

    Deliberately simpler than MeasuredCostModel._measure: a single
    host-synced call per repeat, no chained-scan differencing — the
    sampler runs in-process on the training host where dispatch overhead
    is small, and attribution needs relative per-op scale, not
    protocol-v3 absolute precision.  None when the shard cannot be
    realized locally (caller falls back to the analytic roofline)."""
    import time

    import jax
    import jax.numpy as jnp

    local = op.local_clone(pc)
    if local is None:
        return None
    params = local.init_params(jax.random.PRNGKey(0))
    xs = [jnp.zeros(t.shape, "int32") if t.dtype == "int32"
          else jnp.ones(t.shape, dtype) for t in local.inputs]
    state = local.init_state()

    def loss_of(p, xs_):
        res, _ = local.forward(p, state, xs_, True)
        res = res[0] if isinstance(res, tuple) else res
        return (res.astype("float32") ** 2).sum()

    if params:
        fn = jax.jit(lambda p, xs_: jax.grad(loss_of)(p, xs_))
        args = (params, xs)
    elif op.inputs and op.inputs[0].dtype != "int32":
        fn = jax.jit(lambda xs_: jax.grad(
            lambda x: loss_of({}, x))(list(xs_)))
        args = (xs,)
    else:
        fn = jax.jit(lambda xs_: loss_of({}, xs_))
        args = (xs,)
    jax.block_until_ready(fn(*args))  # compile + warm
    best = None
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best if best and best > 0 else None


@dataclasses.dataclass
class OpProfile:
    name: str
    kind: str
    grid: tuple
    out_shape: tuple
    ms: float            # measured fwd+bwd wall-ms of one shard
    gflops: float        # modeled fwd+bwd GFLOPs of one shard
    measured: bool

    @property
    def tflops_per_sec(self) -> float:
        return (self.gflops / 1e3) / (self.ms / 1e3) if self.ms > 0 else 0.0


class OpProfiler:
    """Per-op timing table for a model (the ``profiling`` flag's output).

    Each op's fwd+grad is jitted in isolation at the shapes ONE device sees
    under the op's ParallelConfig and timed on the local chip.  Isolated
    timings over-count vs the fused step (XLA fuses elementwise ops into
    neighbors), so the table is a per-op *attribution* guide, not an exact
    decomposition — the exact timeline is :func:`trace`.
    """

    def __init__(self, model, repeats: int = 3):
        self.model = model
        self.repeats = repeats

    def profile(self) -> List[OpProfile]:
        from flexflow_tpu.sim.cost_model import (AnalyticCostModel,
                                                 MeasuredCostModel,
                                                 shard_flops)

        measured = MeasuredCostModel(repeats=self.repeats)
        analytic = AnalyticCostModel()
        rows = []
        for op in self.model.layers:
            t = measured._measure(op, op.pc)
            was_measured = t is not None
            if t is None:
                t = analytic.op_cost(op, op.pc)
            gflops = shard_flops(op, op.pc) / 1e9
            rows.append(OpProfile(
                name=op.name, kind=type(op).__name__, grid=op.pc.dims,
                out_shape=op.output.shape, ms=t * 1e3, gflops=gflops,
                measured=was_measured))
        return rows

    def report(self, rows: Optional[List[OpProfile]] = None) -> str:
        rows = rows if rows is not None else self.profile()
        total = sum(r.ms for r in rows)
        lines = [
            f"{'op':<18s} {'kind':<12s} {'grid':<14s} "
            f"{'shard ms':>9s} {'GFLOP':>8s} {'TFLOP/s':>8s} {'%':>5s}",
        ]
        for r in rows:
            pct = 100.0 * r.ms / total if total else 0.0
            mark = "" if r.measured else "~"
            lines.append(
                f"{r.name:<18s} {r.kind:<12s} {str(r.grid):<14s} "
                f"{mark}{r.ms:>8.3f} {r.gflops:>8.2f} "
                f"{r.tflops_per_sec:>8.2f} {pct:>4.1f}%")
        lines.append(f"{'total (isolated, one shard)':<46s} {total:>8.3f} ms"
                     "   [~ = analytic estimate]")
        return "\n".join(lines)


def normalize_cost_analysis(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a dict, empty when the backend
    reports none."""
    return compiled.cost_analysis() or {}


# a Mosaic kernel in optimized HLO text: a custom call to
# ``tpu_custom_call`` whose op_name metadata ends in the kernel's
# pallas_call ``name=`` scope (possibly wrapped, e.g.
# ``transpose(jvp(ff_flash_bwd))/pallas_call``)
_TPU_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
_PALLAS_KERNEL = re.compile(r'op_name="[^"]*?([A-Za-z0-9_.]+)\)*/pallas_call')


def pallas_kernel_calls(hlo_text: str) -> Dict[str, int]:
    """Pallas kernels Mosaic compiled into a program, from its optimized
    HLO text: {kernel name: number of TPU custom calls}.  Empty off the
    TPU (interpret mode lowers kernels to plain HLO) and when nothing
    routed; a call whose kernel carries no ``name=`` counts under
    ``"unnamed"``."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if _TPU_CUSTOM_CALL not in line:
            continue
        m = _PALLAS_KERNEL.search(line)
        name = m.group(1) if m else "unnamed"
        out[name] = out.get(name, 0) + 1
    return out


def compiled_cost(fn, *args) -> Dict[str, float]:
    """FLOPs / bytes for the COMPILED program (XLA cost analysis) — what the
    chip will actually run after fusion, per step."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    ca = normalize_cost_analysis(compiled)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }


def compiled_roofline(compiled, seconds_per_step: Optional[float] = None,
                      perf=None, n_devices: int = 1) -> Dict[str, float]:
    """Roofline summary from an already-compiled executable (no extra
    compile): post-fusion FLOPs/bytes plus, when a measured step time is
    supplied, achieved TFLOP/s and HBM GB/s.  The figures that are a
    fraction of a chip's peak (MXU / HBM utilization, the at-peak floor)
    appear only when ``perf`` names that chip — pass
    ``sim.cost_model.chip_perf(device_kind)`` of the device the time was
    measured on; there is no default chip.

    ``cost_analysis()`` FLOPs are GLOBAL (pre-partitioning) under SPMD, so
    pass ``n_devices`` to compare against the whole machine's peak."""
    ca = normalize_cost_analysis(compiled)
    cost = {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    out = dict(cost)
    timed = bool(seconds_per_step and seconds_per_step > 0)
    if timed:
        out["achieved_tflops"] = cost["flops"] / seconds_per_step / 1e12
        out["achieved_hbm_gbps"] = (
            cost["bytes_accessed"] / seconds_per_step / 1e9)
    if perf is not None:
        peak = perf.peak_flops * max(n_devices, 1)
        hbm = perf.hbm_bandwidth * max(n_devices, 1)
        out["min_step_seconds_at_peak"] = cost["flops"] / peak
        if timed:
            out["mxu_utilization"] = cost["flops"] / seconds_per_step / peak
            out["hbm_utilization"] = (
                cost["bytes_accessed"] / seconds_per_step / hbm)
    return out


def step_roofline(fn, *args, seconds_per_step: Optional[float] = None,
                  perf=None, n_devices: int = 1) -> Dict[str, float]:
    """Roofline summary of a train step (compiles ``fn``); see
    :func:`compiled_roofline`."""
    import jax

    return compiled_roofline(jax.jit(fn).lower(*args).compile(),
                             seconds_per_step, perf, n_devices)
