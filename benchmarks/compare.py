"""The comparison that decides ``correct``: the system against the
configuration's plain reference, on the system's own weights, after the
measured window (a user's run pays no reference pass, so neither does
``setup_s``).

Training: the loss the measured train step reports for the run's batch
from fresh weights, and the gradient that step applied (recovered from
the weights before and after it by the optimizer's own rule), against
the reference's loss and gradient of the same batch, computed in chunks
on one device.  The gradient is held to the reference twice: over
every leaf together, and op by op (a fault in one small op, such as a
kernel gradient doubled by a spatial split, hides in the norm of all).

A gradient recovered from float32 weights is only as fine as the
weights' own spacing: the update's last rounding moves an element by up
to half its spacing, which reads as ``spacing / (2 lr)`` of gradient.
For a LayerNorm gain at 1.0 under a learning rate of 1e-3 that is 3e-5
to 6e-5 an element, the size of the gradient itself.  So each op's
error is taken beyond that floor (``rounding_floor``), and the ops whose
reference gradient lies under their floor are counted as unresolved
rather than judged.  The tolerances are the configuration's
(``tolerance`` in its file, with the reason); the errors measured are
printed on the ``notes`` line of every run.
"""

from __future__ import annotations

from typing import Dict, List, Tuple


def _host(tree):
    import jax
    import numpy as np

    return jax.tree.map(lambda a: np.asarray(a, dtype=np.float32), tree)


def applied_gradient(p0: Dict, p1: Dict, opt: Dict) -> Dict:
    """The gradient a first optimizer step applied: SGD with weight
    decay and zero initial velocity moves p to p - lr (g + wd p)."""
    import jax

    lr, wd = float(opt["learning_rate"]), float(opt.get("weight_decay", 0))
    return jax.tree.map(lambda a, b: (a - b) / lr - wd * a, p0, p1)


def rel_l2(a: Dict, b: Dict) -> float:
    """‖a−b‖/‖b‖ over every leaf together."""
    import jax
    import numpy as np

    def sq(t):
        return float(sum(np.sum(np.square(l, dtype=np.float64))
                         for l in jax.tree.leaves(t)))

    return (sq(jax.tree.map(lambda x, y: x - y, a, b))
            / max(sq(b), 1e-300)) ** 0.5


def rounding_floor(p0: Dict, p1: Dict, opt: Dict) -> Dict:
    """The most, element by element, by which ``applied_gradient`` can
    miss the gradient the step applied: rounding the updated weight to
    float32 moves it by up to half the spacing at its size."""
    import jax
    import numpy as np

    lr = float(opt["learning_rate"])
    return jax.tree.map(
        lambda a, b: 0.5 * np.spacing(np.maximum(np.abs(a), np.abs(b)))
        / lr, p0, p1)


def per_op_errors(g_sys: Dict, g_ref: Dict, floor: Dict) -> Dict[str, Dict]:
    """For each top-level key (an op): ``raw`` = ‖sys−ref‖/‖ref‖,
    ``err`` = the same with the floor's norm taken off the difference
    first (never under 0), and ``resolved``: whether the reference
    gradient is larger than the floor at all."""
    import jax
    import numpy as np

    def norm(t):
        return float(sum(np.sum(np.square(l, dtype=np.float64))
                         for l in jax.tree.leaves(t))) ** 0.5

    out = {}
    for k in g_ref:
        d = norm(jax.tree.map(lambda x, y: x - y, g_sys[k], g_ref[k]))
        r, q = max(norm(g_ref[k]), 1e-300), norm(floor[k])
        out[k] = {"raw": d / r, "err": max(d - q, 0.0) / r,
                  "resolved": r > q}
    return out


def train_step(ctx, built: Dict, fresh_state, step, batch) -> Tuple[
        List[str], Dict]:
    """Problems found (empty when the step agrees with the reference)
    and the errors measured."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tol = ctx.config["tolerance"]
    ref = ctx.reference()
    p0, s0, o0 = fresh_state()
    before = _host(built["op_params"](p0))
    p1, _, _, loss = step(p0, s0, o0, *batch)
    sys_loss = float(loss)
    after = _host(built["op_params"](p1))
    g_sys = applied_gradient(before, after, ctx.config["optimizer"])
    floor = rounding_floor(before, after, ctx.config["optimizer"])
    del p0, p1, after

    n_items = batch[0].shape[0]
    chunk = min(int(ctx.mix.get("reference_chunk", n_items)), n_items)
    params = jax.device_put(jax.tree.map(jnp.asarray, before),
                            ctx.devices[0])
    fn = jax.jit(lambda p, b: ref.sum_loss_and_grads(p, b, ctx.config))
    total, grads, count = 0.0, None, 0
    with jax.default_matmul_precision("highest"):
        for lo in range(0, n_items, chunk):
            part = tuple(jax.device_put(b[lo:lo + chunk], ctx.devices[0])
                         for b in batch)
            loss_c, g, n = fn(params, part)
            total += float(loss_c)
            count += int(n)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    ref_loss = total / count
    g_ref = jax.tree.map(lambda g: np.asarray(g, np.float32) / count, grads)

    loss_err = abs(sys_loss - ref_loss) / max(abs(ref_loss), 1e-30)
    grad_err = rel_l2(g_sys, g_ref)
    per = per_op_errors(g_sys, g_ref, floor)
    judged = {k: v["err"] for k, v in per.items() if v["resolved"]}
    if not judged:
        raise SystemExit("benchmark: every op's gradient lies under the "
                         "weight update's rounding: nothing to compare")
    worst = max(judged, key=judged.get)
    by_err = sorted(judged, key=judged.get, reverse=True)
    notes = {"system_loss": sys_loss, "reference_loss": ref_loss,
             "loss_rel_err": loss_err, "grad_rel_l2": grad_err,
             "worst_op": worst, "worst_op_grad_rel_l2": judged[worst],
             "worst_ops": [[k, judged[k], per[k]["raw"]]
                           for k in by_err[:4]],
             "ops": len(per), "ops_under_rounding_floor": sorted(
                 k for k, v in per.items() if not v["resolved"]),
             "tolerance": {k: tol[k] for k in (
                 "loss_rel", "grad_rel_l2", "op_grad_rel_l2")}}
    problems = []
    if not np.isfinite(sys_loss):
        problems.append(f"the train step's loss is {sys_loss}")
    if not loss_err <= float(tol["loss_rel"]):
        problems.append(f"loss {sys_loss} leaves the reference's "
                        f"{ref_loss} by {loss_err:.3g} (> {tol['loss_rel']})")
    if not grad_err <= float(tol["grad_rel_l2"]):
        problems.append(f"the applied gradient leaves the reference's by "
                        f"{grad_err:.3g} in relative L2 (> "
                        f"{tol['grad_rel_l2']}); worst op {worst}")
    if not judged[worst] <= float(tol["op_grad_rel_l2"]):
        problems.append(f"op {worst}'s applied gradient leaves the "
                        f"reference's by {judged[worst]:.3g} in relative "
                        f"L2 (> {tol['op_grad_rel_l2']})")
    return problems, notes
