"""Counts the multiply-adds a plain reference's forward pass needs, from
its shapes alone: traces the function on shapes (nothing runs), walks the
jaxpr and adds up every convolution and matrix product as 2 x
multiply-adds.  Elementwise work is not counted (it is not what the MXU
peak is a peak of)."""

from __future__ import annotations

import math
from typing import Callable, List, Tuple


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    if name == "conv_general_dilated":
        out = eqn.outvars[0].aval.shape
        rhs = eqn.invars[1].aval.shape
        dn = eqn.params["dimension_numbers"]
        # the kernel's output-feature dimension is in ``out`` already
        taps = math.prod(rhs) / rhs[dn.rhs_spec[0]]
        return 2.0 * math.prod(out) * taps / eqn.params["feature_group_count"]
    if name == "dot_general":
        out = eqn.outvars[0].aval.shape
        (lc, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        return 2.0 * math.prod(out) * math.prod(lhs[d] for d in lc)
    return 0.0


def _walk(jaxpr, out: List[Tuple[str, float]]) -> None:
    for eqn in jaxpr.eqns:
        f = _eqn_flops(eqn)
        if f:
            out.append((eqn.primitive.name, f))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    _walk(inner, out)
                elif hasattr(sub, "eqns"):
                    _walk(sub, out)


def matmul_flops(fn: Callable, *shapes) -> List[Tuple[str, float]]:
    """[(primitive, FLOPs)] of every convolution and matrix product of
    ``fn(*shapes)``, in program order."""
    import jax

    out: List[Tuple[str, float]] = []
    _walk(jax.make_jaxpr(fn)(*shapes).jaxpr, out)
    return out


def cnn_train_flops_per_item(forward: Callable, layers, config,
                             published=(224, 1000)):
    """Forward + backward FLOPs of one image of a CNN reference whose
    parameters are ``layers`` ([(name, kernel shape)]): every layer's
    forward product once forward and twice backward (input and weight
    gradients), except that the first layer's input gradient is not
    needed.  None at other than the ``published`` (image size, classes):
    a rehearsal has no utilization to report."""
    import jax
    import jax.numpy as jnp

    size, classes = int(config["image_size"]), int(config["num_classes"])
    if (size, classes) != tuple(published):
        return None
    f32 = lambda shape: jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
    params = {name: {"kernel": f32(k), "bias": f32(k[-1:])}
              for name, k in layers}
    rows = matmul_flops(forward, params, f32((1, size, size, 3)))
    return 3.0 * sum(f for _, f in rows) - rows[0][1]
