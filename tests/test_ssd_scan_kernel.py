"""The state-space scan's Pallas kernels (``ops/pallas/ssd_scan.py``) in
interpret mode: ``y`` and all six gradients against ``ssd_chunked`` and
against the recurrence over time steps, a sequence the chunk does not
divide, two groups of heads, the compute type's roundings, the rule that
decides which form an ``SSMScan`` runs and the counter that names it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops.base import Tensor
from flexflow_tpu.strategy import ParallelConfig

LEAVES = ("y", "x", "delta", "A_log", "B", "C", "D")
# (steps, heads, chunk): head width 16, state 128
CASES = {"whole_chunks": (256, 8, 128),         # two chunks, one group
         # 56 steps of delta = 0 behind
         "padded_two_groups": (200, 16, 128),
         # the published chunk: the decay matrix in two blocks of rows
         "chunks_of_256": (512, 8, 256)}


def _rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _operands(s, h, p=16, n=128, bsz=2):
    """(x, delta, A_log, B, C, D): time steps of 0.03-0.7 and A of -0.4
    to -2.7, so that a chunk's decays span 1 to 1e-60 and below."""
    return (_rand(0, bsz, s, h, p),
            jax.nn.softplus(_rand(1, bsz, s, h) - 1.0),
            _rand(2, h, scale=0.5), _rand(3, bsz, s, n, scale=0.3),
            _rand(4, bsz, s, n, scale=0.3), _rand(5, h))


def _recurrence(x, dt, a_log, b, c, d):
    """y_t = H_t C_t + D x_t, H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer)
    B_t, a step at a time."""
    a = -jnp.exp(a_log)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[:, :, None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, (jnp.einsum("bhpn,bn->bhp", state, c_t)
                       + d[:, None] * x_t)

    bsz, _, h, p = x.shape
    _, ys = jax.lax.scan(step, jnp.zeros((bsz, h, p, b.shape[-1])),
                         tuple(v.swapaxes(0, 1) for v in (x, dt, b, c)))
    return ys.swapaxes(0, 1)


def _chunked(x, dt, a_log, b, c, d, chunk=128):
    from flexflow_tpu.ops.ssm import ssd_chunked

    return ssd_chunked(x, dt, -jnp.exp(a_log), b, c, d, chunk)


def _kernels(x, dt, a_log, b, c, d, chunk=128):
    from flexflow_tpu.ops.pallas.ssd_scan import ssd_scan

    bsz, s, h, p = x.shape
    xbc = jnp.concatenate([x.reshape(bsz, s, h * p), b, c], axis=-1)
    y = ssd_scan(xbc, dt, -jnp.exp(a_log), d, heads=h, head_dim=p,
                 state=b.shape[-1], chunk=chunk, interpret=True)
    return y.reshape(x.shape)


@functools.lru_cache(maxsize=None)
def _leaves(case, form):
    """{leaf: array}: ``y`` and the gradients of sum(y * weight)."""
    s, h, chunk = CASES[case]
    ops = _operands(s, h)
    weight = _rand(9, *ops[0].shape)
    fn = {"kernels": functools.partial(_kernels, chunk=chunk),
          "chunked": functools.partial(_chunked, chunk=chunk),
          "recurrence": _recurrence}[form]
    value_and_grads = jax.value_and_grad(
        lambda *a: (lambda y: (jnp.sum(y * weight), y))(fn(*a)),
        argnums=range(6), has_aux=True)
    if form != "recurrence":
        # (the recurrence, a step at a time, is slow to compile unrolled)
        value_and_grads = jax.jit(value_and_grads)
    with jax.default_matmul_precision("highest"):
        y, grads = value_and_grads(*ops)
    return dict(zip(LEAVES, (y[1], *grads)))


@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("against", ["chunked", "recurrence"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernels_equal_the_chunked_form_and_the_recurrence(case, against,
                                                           leaf):
    got, want = _leaves(case, "kernels")[leaf], _leaves(case, against)[leaf]
    assert got.shape == want.shape and got.dtype == want.dtype
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * scale)


def _low(ops):
    return tuple(v.astype(jnp.bfloat16) if i in (0, 3, 4) else v
                 for i, v in enumerate(ops))


def _rel(a, b):
    a, b = (v.astype(jnp.float32) for v in (a, b))
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_bfloat16_operands_are_rounded_where_the_chunked_form_rounds_them():
    """The compute type's roundings (m, delta x, the entering state) are
    ssd_chunked's: in bfloat16 the two agree far inside what either
    differs from float32 by."""
    ops = _operands(256, 8)
    got, want = _kernels(*_low(ops)), _chunked(*_low(ops))
    assert got.dtype == want.dtype == jnp.bfloat16
    exact = _chunked(*ops)
    assert _rel(want, exact) > 2e-3
    assert _rel(got, want) < 0.5 * _rel(want, exact)


@functools.lru_cache(maxsize=None)
def _low_grads(form):
    ops = _low(_operands(512, 8))
    weight = _rand(9, *ops[0].shape)
    fn = {"kernels": _kernels, "chunked": _chunked}[form]
    return dict(zip(LEAVES[1:], jax.grad(
        lambda *a: jnp.sum(fn(*a, chunk=256).astype(jnp.float32) * weight),
        argnums=range(6))(*ops)))


@pytest.mark.parametrize("leaf", LEAVES[1:])
def test_bfloat16_gradients_agree_with_the_chunked_form(leaf):
    """With operands that round, every gradient within 1% of
    ``ssd_chunked``'s.  ``A_log``'s is what is left after the sums along
    the rows and down the columns of ``d m * m`` cancel over a chunk: the
    backward kernel takes both from products of the same rounded ``m`` and
    ``delta x`` (with ``delta x`` unrounded in one of them this read 13%
    here and 44% on the chip)."""
    got, want = _low_grads("kernels")[leaf], _low_grads("chunked")[leaf]
    assert got.dtype == want.dtype
    assert _rel(got, want) < 0.01


# ---------------------------------------------------------------------------
# which form an operator takes


def _scan_op(s, h, p, n, chunk, dtype="float32"):
    from flexflow_tpu.ops.ssm import SSMScan

    pc = ParallelConfig((1, 1), (0,))
    return SSMScan("scan", pc, Tensor((2, s, h * p + 2 * n), dtype),
                   Tensor((2, s, h), "float32"), h, p, n, chunk)


def _counted(name):
    from flexflow_tpu import obs

    return obs.snapshot()["counters"].get(name, 0)


@pytest.mark.parametrize("s,h,p,n,chunk,dtype,form", [
    (256, 8, 16, 128, 128, "float32", "pallas"),
    (256, 8, 16, 128, 128, "bfloat16", "pallas"),
    (300, 16, 16, 128, 256, "float32", "pallas"),     # padded to 512
    (128, 8, 16, 128, 256, "float32", "pallas"),      # one chunk of 128
    (10, 3, 4, 5, 1, "float32", "xla_chunked"),
    (10, 3, 4, 5, 3, "float32", "xla_chunked"),
    (10, 3, 4, 5, 10, "float32", "xla_chunked"),
    (10, 3, 4, 5, 64, "float32", "xla_chunked"),
    (256, 8, 16, 128, 64, "float32", "xla_chunked"),  # a chunk under a lane tile
    (256, 4, 32, 128, 128, "float32", "xla_chunked"),  # half a group of heads
    (256, 8, 8, 128, 128, "float32", "xla_chunked"),  # a group of 64 lanes
    (256, 8, 16, 64, 128, "float32", "xla_chunked"),  # a state of 64 lanes
    (256, 8, 32, 256, 128, "float32", "xla_chunked"),  # a state of two tiles
    (256, 8, 16, 128, 128, "float16", "xla_chunked"),
    (2048, 8, 16, 128, 1024, "float32", "xla_chunked"),  # past VMEM's room
    # shapes the kernels could be built for and were never compiled or
    # timed at: the rule holds what it won
    (1024, 8, 16, 128, 512, "bfloat16", "xla_chunked"),
    (768, 8, 16, 128, 384, "bfloat16", "xla_chunked"),
    (256, 8, 128, 128, 128, "bfloat16", "xla_chunked"),  # a head of 128
    (256, 8, 48, 128, 128, "bfloat16", "xla_chunked"),
    (256, 72, 64, 128, 256, "bfloat16", "xla_chunked"),  # wider than 4096
    (512, 64, 64, 128, 256, "bfloat16", "pallas"),    # granite_4_0_h_micro
])
def test_shapes_decide_the_form_and_the_counter_names_it(
        s, h, p, n, chunk, dtype, form, pallas_kernels):
    """With the kernel gate open the shapes alone pick the form (looked at
    while the operator is traced: nothing runs here); with it shut every
    shape keeps ``xla_chunked``."""
    op = _scan_op(s, h, p, n, chunk, dtype)
    params = jax.eval_shape(op.init_params, jax.random.PRNGKey(0))
    xs = [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in op.inputs]
    run = lambda: jax.eval_shape(
        lambda p_, x: op.forward(p_, {}, x, True)[0], params, xs)
    names = {f: f"kernels.ssd.{f}.{op.chunk}x{h}x{n}"
             for f in ("pallas", "xla_chunked")}
    before = {f: _counted(k) for f, k in names.items()}
    with pallas_kernels():
        out = run()
    assert out.shape == (2, s, h * p) and out.dtype == jnp.dtype(dtype)
    other = "xla_chunked" if form == "pallas" else "pallas"
    assert _counted(names[form]) == before[form] + 1
    assert _counted(names[other]) == before[other]
    run()                               # the gate shut: the CPU's form
    assert _counted(names["xla_chunked"]) \
        == before["xla_chunked"] + 1 + (form == "xla_chunked")
    assert _counted(names["pallas"]) == before["pallas"] + (form == "pallas")


def test_operator_through_the_kernels_equals_the_operator_without(
        pallas_kernels):
    """``SSMScan.forward`` with the gate open and shut on the same
    operands: one operator, two forms, one result."""
    op = _scan_op(256, 8, 16, 128, 128)
    params = op.init_params(jax.random.PRNGKey(0))
    params["D"] = _rand(5, 8)
    xs = [_rand(1, 2, 256, 8 * 16 + 256, scale=0.5),
          jax.nn.softplus(_rand(2, 2, 256, 8) - 1.0)]
    f = lambda p, xs: jnp.sum(op.forward(p, {}, xs, True)[0] ** 2)
    with pallas_kernels():
        got = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(params, xs)
    want = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(params, xs)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(b))))


def test_kernels_refuse_shapes_that_are_not_theirs():
    from flexflow_tpu.ops.pallas.ssd_scan import ssd_scan

    with pytest.raises(ValueError, match="not the kernels' shapes"):
        ssd_scan(jnp.zeros((2, 10, 3 * 4 + 10)), jnp.zeros((2, 10, 3)),
                 -jnp.ones((3,)), jnp.ones((3,)), heads=3, head_dim=4,
                 state=5, chunk=3)
