"""Per-op numeric parity vs plain jax/numpy references (SURVEY.md §4 test
pyramid level 1), on the 8-device CPU mesh with non-trivial grids."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.ops import Conv2D, Pool2D, Linear, Flat, Softmax, Concat
from flexflow_tpu.ops.norm import BatchNorm
from flexflow_tpu.ops.base import Tensor
from flexflow_tpu.ops.pool import POOL_AVG
from flexflow_tpu.strategy import ParallelConfig


def pc4(w=1, h=1, c=1, n=1, devs=None):
    total = w * h * c * n
    return ParallelConfig((w, h, c, n),
                          tuple(devs) if devs else tuple(range(total)))


def run(op, xs, params=None, state=None, train=True):
    params = params if params is not None else op.init_params(
        jax.random.PRNGKey(0))
    state = state if state is not None else op.init_state()
    y, st = op.forward(params, state, xs, train)
    return np.asarray(y), params, st


def test_conv2d_matches_lax():
    x = jnp.asarray(np.random.RandomState(0).randn(4, 12, 12, 3),
                    dtype=jnp.float32)
    t = Tensor((4, 12, 12, 3))
    op = Conv2D("c", pc4(n=1), t, out_channels=8, kernel_h=3, kernel_w=3,
                stride_h=2, stride_w=2, padding_h=1, padding_w=1, relu=True)
    assert op.output.shape == (4, 6, 6, 8)
    y, params, _ = run(op, [x])
    ref = jax.lax.conv_general_dilated(
        x, params["kernel"], (2, 2), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    ref = jax.nn.relu(ref + params["bias"])
    np.testing.assert_allclose(y, np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_pool2d_max_and_avg():
    x = jnp.asarray(np.random.RandomState(1).randn(2, 6, 6, 4),
                    dtype=jnp.float32)
    t = Tensor((2, 6, 6, 4))
    op = Pool2D("p", pc4(), t, 2, 2, 2, 2, 0, 0, relu=False)
    y, _, _ = run(op, [x])
    ref = np.asarray(x).reshape(2, 3, 2, 3, 2, 4).max(axis=(2, 4))
    np.testing.assert_allclose(y, ref, rtol=1e-6)

    op = Pool2D("p2", pc4(), t, 2, 2, 2, 2, 0, 0, pool_type=POOL_AVG,
                relu=False)
    y, _, _ = run(op, [x])
    ref = np.asarray(x).reshape(2, 3, 2, 3, 2, 4).mean(axis=(2, 4))
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-6)


def _np_pool(x, g, kh, kw, sh, sw, ph, pw, pool_type, relu):
    """Pool2D's forward and input gradient under the cotangent ``g``, in
    float64 loops (no ``reduce_window``): MAX takes the first maximum in
    window order (rows, then columns) and sends the whole gradient
    there; AVG divides by the count of positions inside the image."""
    x = np.asarray(x, np.float64)
    g = np.asarray(g, np.float64)
    n, h, w, c = x.shape
    oh, ow = 1 + (h + 2 * ph - kh) // sh, 1 + (w + 2 * pw - kw) // sw
    xp = np.full((n, h + 2 * ph, w + 2 * pw, c),
                 -np.inf if pool_type == "max" else 0.0)
    xp[:, ph:ph + h, pw:pw + w] = x
    inside = np.zeros(xp.shape[1:3])
    inside[ph:ph + h, pw:pw + w] = 1.0
    y = np.zeros((n, oh, ow, c))
    dxp = np.zeros_like(xp)
    ni, ci = np.meshgrid(np.arange(n), np.arange(c), indexing="ij")
    for i in range(oh):
        for j in range(ow):
            rows = slice(i * sh, i * sh + kh)
            cols = slice(j * sw, j * sw + kw)
            win = xp[:, rows, cols].reshape(n, kh * kw, c)
            if pool_type == "max":
                out = win.max(axis=1)
                first = win.argmax(axis=1)      # numpy: first occurrence
                gij = g[:, i, j] * (out > 0 if relu else 1.0)
                np.add.at(dxp, (ni, i * sh + first // kw,
                                j * sw + first % kw, ci), gij)
            else:
                count = inside[rows, cols].sum()
                out = win.sum(axis=1) / count
                gij = g[:, i, j] * (out > 0 if relu else 1.0)
                dxp[:, rows, cols] += (gij / count)[:, None, None, :] \
                    * inside[rows, cols][None, :, :, None]
            y[:, i, j] = np.maximum(out, 0.0) if relu else out
    return y, dxp[:, ph:ph + h, pw:pw + w]


def _pool_case(shape, geometry, pool_type, relu, dtype, seed, integers):
    kh, kw, sh, sw, ph, pw = geometry
    rng = np.random.RandomState(seed)
    # small integers: every window ties (and they are exact in
    # bfloat16); negatives reach the fused ReLU's clamp
    x = (rng.randint(-3, 4, size=shape) if integers
         else rng.randn(*shape)).astype(np.float32)
    op = Pool2D("p", pc4(), Tensor(shape, dtype), kh, kw, sh, sw, ph, pw,
                pool_type=pool_type, relu=relu)
    x = jnp.asarray(x, dtype)
    g = jnp.asarray(rng.randn(*op.output.shape), dtype)
    y, vjp = jax.vjp(lambda x: op.forward({}, {}, [x], True)[0], x)
    (dx,) = vjp(g)
    assert y.dtype == dx.dtype == jnp.dtype(dtype)
    return (np.asarray(y, np.float64), np.asarray(dx, np.float64),
            *_np_pool(x, g, kh, kw, sh, sw, ph, pw, pool_type, relu))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n,h,w,c,k,p,relu", [
    (2, 9, 9, 3, 3, 0, False),    # odd extents, VALID (Inception pools)
    (2, 16, 16, 5, 3, 0, True),   # even extents + fused relu
    (3, 15, 17, 4, 3, 1, True),   # pad 1 (ResNet/DenseNet pool1), h != w
    (2, 12, 12, 3, 2, 0, False),  # 2x2 (VGG pools)
    (1, 8, 8, 2, 3, 1, False),    # tiny single-sample
    (2, 23, 19, 6, 3, 0, True),   # ragged H/W
])
def test_pool2d_max_forward_and_gradient(n, h, w, c, k, p, relu, dtype):
    y, dx, y_ref, dx_ref = _pool_case(
        (n, h, w, c), (k, k, 2, 2, p, p), "max", relu, dtype, seed=0,
        integers=True)
    np.testing.assert_array_equal(y, y_ref)
    # up to four windows send their gradient to one element; bfloat16
    # rounds each sum
    tol = 1e-6 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(dx, dx_ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("n,h,w,c,kh,kw,sh,sw,ph,pw", [
    (2, 8, 8, 16, 8, 8, 1, 1, 0, 0),    # global pool (Inception tail)
    (4, 8, 8, 3, 2, 2, 2, 2, 0, 0),     # 2x2 exact tiling
    (2, 12, 9, 24, 3, 3, 3, 3, 0, 0),   # 3x3 tiling, h != w
    (2, 9, 7, 5, 3, 3, 1, 1, 1, 1),     # Inception's in-block pool: the
                                        # count is 4, 6 or 9 at the border
])
def test_pool2d_avg_forward_and_gradient(n, h, w, c, kh, kw, sh, sw, ph, pw,
                                         relu):
    y, dx, y_ref, dx_ref = _pool_case(
        (n, h, w, c), (kh, kw, sh, sw, ph, pw), POOL_AVG, relu, "float32",
        seed=11, integers=False)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-5, atol=1e-6)


def _np_batchnorm(x, scale, bias, g, relu, eps):
    """Training-mode batch norm with its three gradients under the
    cotangent ``g``, through the batch statistics, in float64."""
    x, scale, bias, g = (np.asarray(a, np.float64)
                         for a in (x, scale, bias, g))
    mean = x.mean(axis=(0, 1, 2))
    var = x.var(axis=(0, 1, 2))
    std = np.sqrt(var + eps)
    xhat = (x - mean) / std
    y = xhat * scale + bias
    dy = g * (y > 0) if relu else g
    m = x.size // x.shape[-1]
    dxhat = dy * scale
    dx = (dxhat - dxhat.sum(axis=(0, 1, 2)) / m
          - xhat * (dxhat * xhat).sum(axis=(0, 1, 2)) / m) / std
    return (np.maximum(y, 0.0) if relu else y, mean, var, dx,
            (dy * xhat).sum(axis=(0, 1, 2)), dy.sum(axis=(0, 1, 2)))


@pytest.mark.parametrize("n,h,w,c,relu,dtype", [
    (4, 4, 4, 16, False, "float32"), (4, 4, 4, 16, True, "float32"),
    (4, 4, 4, 130, False, "float32"), (4, 4, 4, 130, True, "float32"),
    (8, 1, 1, 7, False, "float32"), (8, 1, 1, 7, True, "float32"),
    (4, 4, 4, 16, True, "bfloat16"),
])
def test_batchnorm_forward_statistics_and_gradients(n, h, w, c, relu, dtype):
    rng = np.random.RandomState(13)
    op = BatchNorm("bn", pc4(), Tensor((n, h, w, c), dtype), relu=relu)
    x = jnp.asarray(rng.randn(n, h, w, c) * 2 + 0.5, dtype)
    g = jnp.asarray(rng.randn(n, h, w, c), dtype)
    params = {"scale": jnp.asarray(1 + 0.3 * rng.randn(c), jnp.float32),
              "bias": jnp.asarray(0.2 * rng.randn(c), jnp.float32)}
    state = op.init_state()
    (y, new_state), vjp = jax.vjp(
        lambda p, x: op.forward(p, state, [x], True), params, x)
    dparams, dx = vjp((g, jax.tree.map(jnp.zeros_like, new_state)))
    # cotangents keep the primals' types: the activation's gradient is
    # the compute type's, the parameters' float32
    assert y.dtype == dx.dtype == jnp.dtype(dtype)
    assert dparams["scale"].dtype == dparams["bias"].dtype == jnp.float32
    y_ref, mean, var, dx_ref, dscale, dbias = _np_batchnorm(
        x, params["scale"], params["bias"], g, relu, op.eps)
    m = op.momentum
    np.testing.assert_allclose(new_state["mean"], (1 - m) * mean,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(new_state["var"], m + (1 - m) * var,
                               rtol=1e-5, atol=1e-6)
    # bfloat16: one pass y = x * inv + shift in the compute type
    tol = 2e-4 if dtype == "float32" else 6e-2
    for got, want in ((y, y_ref), (dx, dx_ref), (dparams["scale"], dscale),
                      (dparams["bias"], dbias)):
        np.testing.assert_allclose(
            np.asarray(got, np.float64), want, rtol=tol,
            atol=tol * max(1.0, np.abs(want).max()))


def test_linear_matches_numpy():
    x = jnp.asarray(np.random.RandomState(2).randn(8, 16), dtype=jnp.float32)
    t = Tensor((8, 16))
    op = Linear("l", ParallelConfig((1, 1), (0,)), t, 32, relu=True)
    y, params, _ = run(op, [x])
    ref = np.maximum(np.asarray(x) @ np.asarray(params["kernel"])
                     + np.asarray(params["bias"]), 0)
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)


def test_flat():
    x = jnp.arange(2 * 3 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 3, 4)
    op = Flat("f", ParallelConfig((1, 1), (0,)), Tensor((2, 3, 3, 4)))
    y, _, _ = run(op, [x])
    assert y.shape == (2, 36)
    np.testing.assert_allclose(y, np.asarray(x).reshape(2, 36))


def test_softmax_loss():
    logits = jnp.asarray(np.random.RandomState(3).randn(8, 10),
                         dtype=jnp.float32)
    labels = jnp.asarray(np.arange(8) % 10, dtype=jnp.int32)
    op = Softmax("s", ParallelConfig((1,), (0,)), Tensor((8, 10)))
    lp, _, _ = run(op, [logits])
    loss = float(op.loss(jnp.asarray(lp), labels))
    e = np.exp(np.asarray(logits) - np.asarray(logits).max(1, keepdims=True))
    p = e / e.sum(1, keepdims=True)
    ref = -np.mean(np.log(p[np.arange(8), np.asarray(labels)]))
    assert abs(loss - ref) < 1e-5


def test_concat():
    a = jnp.ones((2, 3, 3, 4))
    b = jnp.zeros((2, 3, 3, 2))
    op = Concat("cat", pc4(), [Tensor((2, 3, 3, 4)), Tensor((2, 3, 3, 2))])
    assert op.output.shape == (2, 3, 3, 6)
    y, _, _ = run(op, [a, b])
    assert y.shape == (2, 3, 3, 6)
    np.testing.assert_allclose(y[..., :4], 1.0)
    np.testing.assert_allclose(y[..., 4:], 0.0)


def test_batchnorm_train_normalizes():
    x = jnp.asarray(np.random.RandomState(4).randn(8, 4, 4, 3) * 5 + 2,
                    dtype=jnp.float32)
    op = BatchNorm("bn", pc4(), Tensor((8, 4, 4, 3)), relu=False)
    y, params, st = run(op, [x])
    assert abs(y.mean()) < 1e-4
    assert abs(y.std() - 1.0) < 1e-2
    # running stats moved toward batch stats
    assert np.all(np.asarray(st["mean"]) != 0.0)


def test_sharded_op_matches_single_device(machine8):
    """Same conv numeric result whether computed unsharded or under a
    nontrivial {w,h,c,n} grid (partition-invariance at the op level)."""
    from jax.sharding import PartitionSpec as P

    x_np = np.random.RandomState(5).randn(8, 8, 8, 4).astype(np.float32)
    t = Tensor((8, 8, 8, 4))
    op = Conv2D("c", pc4(w=2, h=2, c=1, n=2), t, 8, 3, 3, 1, 1, 1, 1,
                relu=True)
    params = op.init_params(jax.random.PRNGKey(0))

    y_plain = np.asarray(op.forward(params, {}, [jnp.asarray(x_np)], True)[0])

    sh = op.output_sharding(machine8)
    xin = jax.device_put(x_np, machine8.sharding(
        op.pc, op.AXIS_NAMES, P("n", "h", "w", None)))

    @jax.jit
    def f(p, x):
        y, _ = op.forward(p, {}, [x], True)
        return jax.lax.with_sharding_constraint(y, sh)

    y_sharded = np.asarray(f(params, xin))
    np.testing.assert_allclose(y_sharded, y_plain, rtol=1e-4, atol=1e-5)
