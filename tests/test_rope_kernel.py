"""``ff_rope`` (``ops/pallas/rope.py``, PR 37) in interpret mode: rotary
positions in the row-major ``(B, S, heads * head_dim)`` layout, a head's
pairs brought together on the MXU inside the kernel.  Held to
``apply_rope(.., "split")`` of the same product (the statement of the
mathematics), alone and inside ``GroupedQueryAttention``, with the
parameters and their gradients in their stored order; the rule's
refusals; and Granite's operator, which has no positions, held to the
program the parent commit traced."""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.ops.base import Tensor
from flexflow_tpu.ops.pallas import rope
from flexflow_tpu.ops.seq_gated import apply_rope, rotary_table
from flexflow_tpu.strategy import ParallelConfig

# laguna_s_2_1's two rules at a head of 128: the sliding layers turn all
# of it at theta 10 000, the full layers 64 of it under YaRN
RULES = {
    "sliding": {"dim": 128, "rope_theta": 10000.0},
    "full": {"dim": 64, "rope_theta": 500000.0, "rope_type": "yarn",
             "factor": 32.0, "original_max_position_embeddings": 4096,
             "beta_fast": 32.0, "beta_slow": 1.0},
}
HD = 128


def _rand(seed, *shape, dtype="float32", scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       dtype)


def _counted(name):
    from flexflow_tpu import obs

    return obs.snapshot()["counters"].get(name, 0)


def _counted_under(prefix):
    from flexflow_tpu import obs

    return sum(v for k, v in obs.snapshot()["counters"].items()
               if k.startswith(prefix))


@pytest.fixture
def small_blocks(monkeypatch):
    """Row blocks of 64: a sequence of 88 is a whole block and a ragged
    one."""
    monkeypatch.setattr(rope, "_ROWS", 64)
    rope._make_rope.cache_clear()
    yield
    rope._make_rope.cache_clear()


def _proj(x, w):
    return jnp.einsum("bsd,de->bse", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


@pytest.mark.parametrize("interpreter", ["generic", "tpu"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rule", sorted(RULES))
def test_kernel_against_apply_rope_of_the_same_product(
        rule, dtype, interpreter, small_blocks):
    """Values, and the gradients of the input and of the weight in its
    stored column order, under one cotangent.  float32: to the last bits
    (XLA's CPU backend contracts a multiply and an add where it likes, in
    either program, so two units of the largest operand's last place);
    bfloat16: within one rounding of the result."""
    rule = RULES[rule]
    b, s, heads, d = 2, 88, 3, 48
    x = _rand(0, b, s, d, dtype=dtype)
    w = _rand(1, d, heads * HD, dtype=dtype, scale=d ** -0.5)
    ct = _rand(2, b, s, heads * HD)
    cos, sin = rotary_table(rule, s)
    interpret = True if interpreter == "generic" else pltpu.InterpretParams()

    def plain(x, w):
        return apply_rope(_proj(x, w).reshape(b, s, heads, HD), cos, sin
                          ).reshape(b, s, heads * HD)

    def kernel(x, w):
        return rope.rope_packed(_proj(x, w), cos, sin, heads,
                                interpret=interpret)

    def both(f):
        return jax.value_and_grad(
            lambda x, w: jnp.sum(f(x, w).astype(jnp.float32) * ct),
            (0, 1))(x, w)

    got, want = kernel(x, w), plain(x, w)
    assert got.dtype == want.dtype and got.shape == want.shape
    grow = float(jnp.max(jnp.abs(cos)))     # YaRN's factor, or 1
    last = 2.0 ** -23 if dtype == "float32" else 2.0 ** -8
    top = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=0, atol=2 * last * top * grow)
    (_, got_grads), (_, want_grads) = both(kernel), both(plain)
    for g, w_, name in zip(got_grads, want_grads, ("x", "w")):
        scale = float(jnp.max(jnp.abs(w_.astype(jnp.float32))))
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w_, np.float32), rtol=0,
            atol=(1e-5 if dtype == "float32" else 2.0 ** -6) * scale,
            err_msg=name)


def test_pairing_matrix_is_the_deinterleave_and_its_transpose_undoes_it():
    x = jnp.arange(2 * 8, dtype=jnp.float32).reshape(2, 8)
    p, back = (np.asarray(rope._pairing(8, 2, b), np.float32)
               for b in (False, True))
    np.testing.assert_array_equal(          # 4 of 8 turned
        x @ p, np.asarray(x)[:, [0, 2, 1, 3, 4, 5, 6, 7]])
    np.testing.assert_array_equal(back, p.T)
    np.testing.assert_array_equal(p @ back, np.eye(8))
    np.testing.assert_array_equal(
        x @ np.asarray(rope._pairing(8, 4, False), np.float32),
        np.asarray(x)[:, [0, 2, 4, 6, 1, 3, 5, 7]])
    # a float32 operand crosses the bfloat16 product whole
    v = _rand(3, 4, 8) * 1e3
    np.testing.assert_array_equal(
        rope._permuted(v, rope._pairing(8, 2, False)),
        np.asarray(v)[:, [0, 2, 1, 3, 4, 5, 6, 7]])


def test_the_tables_zero_what_a_roll_brings_from_outside_its_pair():
    cos, sin = rotary_table(RULES["full"], 5)
    c, sp, sm = rope.tables(cos, sin, HD)
    assert c.shape == sp.shape == sm.shape == (5, HD)
    np.testing.assert_array_equal(c[:, :32], cos)
    np.testing.assert_array_equal(c[:, 32:64], cos)
    np.testing.assert_array_equal(c[:, 64:], 1.0)
    np.testing.assert_array_equal(sp[:, 32:64], sin)
    np.testing.assert_array_equal(sm[:, :32], -sin)
    assert not np.any(sp[:, :32]) and not np.any(sp[:, 64:])
    assert not np.any(sm[:, 32:])


def test_body_is_traced_once_a_configuration(small_blocks):
    """Two layers, and the backward pass of each, bind one trace of the
    forward kernel and one of the backward."""
    cos, sin = rotary_table(RULES["sliding"], 64)
    q = _rand(4, 1, 64, 2 * HD)
    traced = [_counted("kernels.traced.ff_rope" + t) for t in ("", "_t")]

    def two_layers(q):
        once = rope.rope_packed(q, cos, sin, 2, interpret=True)
        return jnp.sum(rope.rope_packed(once, cos, sin, 2, interpret=True))

    jax.jit(jax.grad(two_layers))(q)
    assert [_counted("kernels.traced.ff_rope" + t) for t in ("", "_t")] \
        == [n + 1 for n in traced]


def _attention(hd, rule, window, heads=4, kv=2, d=32, s=40):
    from flexflow_tpu.ops.attention import GroupedQueryAttention

    return GroupedQueryAttention(
        "attn", ParallelConfig((1, 1, 1), (0,)), Tensor((2, s, d), "float32"),
        heads, kv, hd, hd ** -0.5, rope=rule, window=window, gate=True)


@pytest.mark.parametrize("rule,window", [("sliding", 16), ("full", None)])
def test_operator_is_the_same_function_of_its_parameters_on_both_paths(
        rule, window, pallas_kernels, small_blocks, monkeypatch):
    """The kernel gate open on both sides (the scores run in the flash
    kernels); ``fits`` refusing on one: the result and the gradient of
    every matrix, in its stored order, and of the input."""
    rule = RULES[rule]
    op = _attention(HD, rule, window)
    params = op.init_params(jax.random.PRNGKey(0))
    assert set(params) == {"wq", "wk", "wv", "wo", "wg"}
    x, ct = _rand(5, 2, 40, 32), _rand(6, 2, 40, 32)
    name = f"x{HD}r{rule['dim']}"

    def run():
        return jax.value_and_grad(
            lambda p, x: jnp.sum(op.forward(p, {}, [x], True)[0] * ct),
            (0, 1))(params, x)

    with pallas_kernels():
        before = {k: _counted(f"kernels.rope.{k}{name}")
                  for k in ("pallas.4", "pallas.2", "xla.4", "xla.2")}
        got, (got_p, got_x) = run()
        assert _counted(f"kernels.rope.pallas.4{name}") \
            == before["pallas.4"] + 1
        assert _counted(f"kernels.rope.pallas.2{name}") \
            == before["pallas.2"] + 1
        assert _counted(f"kernels.rope.xla.4{name}") == before["xla.4"]
        monkeypatch.setattr(rope, "fits", lambda *a: False)
        want, (want_p, want_x) = run()
        assert _counted(f"kernels.rope.xla.4{name}") == before["xla.4"] + 1
        assert _counted(f"kernels.rope.xla.2{name}") == before["xla.2"] + 1
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got_x, want_x, rtol=1e-4, atol=1e-6)
    for key in ("wq", "wk", "wv", "wo", "wg"):
        np.testing.assert_allclose(got_p[key], want_p[key], rtol=1e-4,
                                   atol=1e-6, err_msg=key)
    assert float(jnp.max(jnp.abs(got_p["wq"]))) > 1e-3


@pytest.mark.parametrize("head_dim,rotated,dtype,why", [
    (192, 64, "bfloat16", "a head that is no whole number of lane tiles"),
    (128, 63, "bfloat16", "an odd number of turned dimensions"),
    (128, 130, "bfloat16", "more turned dimensions than the head has"),
    (384, 128, "bfloat16", "a head wider than any that was compiled"),
    (128, 128, "float16", "a type the kernel was not run in"),
])
def test_rule_refuses(head_dim, rotated, dtype, why, pallas_kernels):
    with pallas_kernels():
        assert rope.fits(128, 128, "bfloat16")
        assert rope.fits(128, 64, "float32")
        assert rope.fits(256, 2, "bfloat16")
        assert not rope.fits(head_dim, rotated, dtype), why
    assert not rope.fits(128, 128, "bfloat16"), "the gate is shut on a CPU"


@pytest.mark.parametrize("head_dim,rotated", [(192, 64), (64, 32)])
def test_refused_shapes_keep_apply_rope_in_the_operator(head_dim, rotated,
                                                        pallas_kernels):
    op = _attention(head_dim, {"dim": rotated, "rope_theta": 1e4}, None)
    params = op.init_params(jax.random.PRNGKey(1))
    name = f"x{head_dim}r{rotated}"
    pallas, xla = (_counted_under("kernels.rope.pallas."),
                   _counted(f"kernels.rope.xla.4{name}"))
    with pallas_kernels():
        jax.eval_shape(lambda p, x: op.forward(p, {}, [x], True)[0], params,
                       jax.ShapeDtypeStruct((2, 40, 32), jnp.float32))
    assert _counted_under("kernels.rope.pallas.") == pallas
    assert _counted(f"kernels.rope.xla.4{name}") == xla + 1
    assert _counted(f"kernels.rope.xla.2{name}") >= 1


def test_wrong_shapes_are_refused_by_the_call_itself():
    cos, sin = rotary_table(RULES["sliding"], 8)
    with pytest.raises(ValueError, match="not the kernel's shapes"):
        rope.rope_packed(_rand(7, 1, 8, 192), cos, sin, 1, interpret=True)
    with pytest.raises(ValueError, match="not the kernel's shapes"):
        rope.rope_packed(_rand(7, 1, 8, 128), *rotary_table(
            {"dim": 256, "rope_theta": 1e4}, 8), 1, interpret=True)


# granite_4_0_h_micro's attention layer (32 query heads of 64 on 8, no
# positions, window or gate) at the cell's shape: the jaxpr of its forward
# and gradient with the kernel gate open, as the parent commit (PR 36)
# traced it
_GRANITE = ((2, 8192, 2048), 32, 8, 64, 0.015625)
_GRANITE_DIGEST = "4cb7f2428fd568f5"


def test_operator_without_positions_traces_to_the_parents_program(
        pallas_kernels, monkeypatch):
    from flexflow_tpu.ops.attention import GroupedQueryAttention

    fa = importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    shape, heads, kv, hd, scale = _GRANITE
    op = GroupedQueryAttention("attn", ParallelConfig((1, 1, 1), (0,)),
                               Tensor(shape, "bfloat16"), heads, kv, hd,
                               scale)
    params = {k: jax.ShapeDtypeStruct(v, jnp.float32)
              for k, v in op._shapes().items()}
    before = _counted_under("kernels.rope.")

    def step(params, x):
        return jax.value_and_grad(
            lambda p, x: op.forward(p, {}, [x], True)[0].astype(
                jnp.float32).sum(), (0, 1))(params, x)

    with pallas_kernels():
        text = str(jax.make_jaxpr(step)(
            params, jax.ShapeDtypeStruct(shape, jnp.bfloat16)))
    assert "ff_flash_fwd" in text and "ff_rope" not in text
    assert _counted_under("kernels.rope.") == before
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _GRANITE_DIGEST
