"""Rotary positions as one Pallas TPU kernel, ``ff_rope``, on the row-major
``(B, S, heads * head_dim)`` array that a projection writes and the flash
kernels read: one read and one write of the array a pass, no relayout.

``ops/seq_gated.apply_rope(.., "split")`` de-interleaves a head's turned
dimensions ``(x0, x2, .. | x1, x3, ..)`` and turns the halves against each
other.  On the 4-D view ``(B, S, heads, head_dim)`` XLA pays for that in
layouts: the view is tiled over ``(heads, head_dim)`` where the product
is tiled over ``(S, heads * head_dim)``, and a de-interleave along lanes
wants a third (``T(2,128)``): between the q product and ``ff_flash_fwd``
the Laguna cell's sliding layers moved 302-604 MB six times, in float32
(PERF.md section 6, PR 37).  Here a grid step holds whole heads in VMEM
and, a head at a time:

* the de-interleave is a product with a 0/1 matrix on the MXU, which has
  nothing else to do in this kernel: ``x P`` is exact (one term a column)
  and arrives in float32, the type the rotation wants;
* the rotation is ``x * C + roll(x, +half) * Sp + roll(x, -half) * Sm``
  along the head's own lanes, with three float32 tables of ``(S,
  head_dim)`` that :func:`tables` makes from ``rotary_table``'s cos and
  sin: ``C`` cos on the turned lanes and 1 on those that pass through,
  ``Sp`` +sin on the second half of the turned lanes, ``Sm`` -sin on the
  first half, both 0 elsewhere, so what a roll brings in from beyond its
  pair meets a zero.  Rounded once to the operands' type, as
  ``apply_rope`` rounds once.

The rotation is orthogonal up to YaRN's factor, which is inside the
tables, so its transpose is the rotation by the opposite angle, and the
permutation's is its inverse: the backward pass, ``ff_rope_t``, is the
same body the other way round (turn by ``-Sp`` and ``-Sm``, round, then
``P^T``), and nothing is kept for it but the tables.  The parameters never
see any of it: ``wq`` and ``wk`` are multiplied as they are stored.
(Permuting their columns instead, so that the product lands
de-interleaved, costs 2.1 ms more a sliding layer's forward and backward
on the chip than the MXU does here; PERF.md section 6, PR 37.)

A grid step is one (sequence, block of rows, block of whole heads), the
heads innermost so that a row block's tables are fetched once.  Which
shapes take the kernel is :func:`fits`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu import obs
# the gate is looked up on the package at every call: tests patch it there
from flexflow_tpu.ops import pallas as _package
from flexflow_tpu.ops.pallas import traced_once
from flexflow_tpu.ops.pallas.ssd_scan import _pieces

LANES = 128
_F32 = jnp.float32
#: rows of a sequence a grid step holds, and the most rows of one product
#: with the permutation inside it (the MXU loads the matrix again for
#: every product: at 32 rows a pass took 1.28 ms where 1.01 at 256, my
#: chip run, PR 37); tests cut the first to their sizes
_ROWS = 512
_SUB_ROWS = 256
#: the most lanes a grid step holds: 8 heads of 128
_MAX_BLOCK_LANES = 1024
#: the widest head the rule takes (compiled; 128 is what the chip timed)
MAX_HEAD_DIM = 2 * LANES


def fits(head_dim: int, rotated: int, dtype) -> bool:
    """Whether ``ff_rope`` turns heads of these shapes: the one gate
    (``ops/pallas.flash_enabled``: the backend is a TPU); a head that is a
    whole number of 128-lane tiles, so that heads sit on lane tiles and a
    roll stays inside one head's block; an even number of turned
    dimensions, at most the head's; bfloat16 or float32 operands.  It
    holds what was run: the Laguna cell's two shapes (128 of 128 and 64
    of 128 turned, bfloat16) are the ones timed on the chip; a head of
    256, float32 and the narrowest turn are compiled by Mosaic in
    ``tests/test_kernels_compile_for_v5e.py`` and not timed.  Everything
    else keeps ``apply_rope``: latent attention's 64 turned dimensions at
    offset 128 of a 192-wide head sit on no lane tile."""
    return (_package.flash_enabled()
            and head_dim % LANES == 0 and 0 < head_dim <= MAX_HEAD_DIM
            and rotated % 2 == 0 and 0 < rotated <= head_dim
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def tables(cos, sin, head_dim: int):
    """``C``, ``Sp`` and ``Sm``, each (seq, head_dim) float32, from
    ``rotary_table``'s cos and sin (seq, rotated / 2)."""
    s, half = cos.shape
    zero = jnp.zeros((s, half), _F32)
    rest = head_dim - 2 * half
    c = jnp.concatenate([cos, cos, jnp.ones((s, rest), _F32)], axis=1)
    sp = jnp.concatenate([zero, sin, jnp.zeros((s, rest), _F32)], axis=1)
    sm = jnp.concatenate([-sin, zero, jnp.zeros((s, rest), _F32)], axis=1)
    return c, sp, sm


def _pairing(head_dim, half, back):
    """(head_dim, head_dim) of 0 and 1, bfloat16: ``x @ it`` brings column
    ``2 i`` of the turned ones to ``i`` and ``2 i + 1`` to ``half + i``
    and leaves the rest; ``back`` its transpose, which undoes that."""
    old = jax.lax.broadcasted_iota(jnp.int32, (head_dim, head_dim), 0)
    new = jax.lax.broadcasted_iota(jnp.int32, (head_dim, head_dim), 1)
    if back:
        old, new = new, old
    source = jnp.where(new < half, 2 * new,
                       jnp.where(new < 2 * half, 2 * (new - half) + 1, new))
    return (old == source).astype(jnp.bfloat16)


def _permuted(x, p):
    """``x p`` in float32, exactly: a column of ``p`` holds a single 1, so
    a bfloat16 ``x`` passes whole and a float32 one as its three pieces."""
    if x.dtype == jnp.bfloat16:
        return jnp.dot(x, p, preferred_element_type=_F32)
    return sum(jnp.dot(piece, p, preferred_element_type=_F32)
               for piece in _pieces(x))


@functools.partial(jax.jit, static_argnames=("half", "back", "dtype"))
def _turn_head(x, c, sp, sm, p, *, half, back, dtype):
    """One head's rows (rows, head_dim) turned, or turned back.  Under a
    ``jit`` of its own so that its Python runs once for all the heads of
    every kernel body of a rule (Mosaic inlines it): the chip machine's
    host traces slowly, and that is ``setup_s``."""
    head_dim = x.shape[1]
    x = x.astype(_F32) if back else _permuted(x, p)
    there = pltpu.roll(x, half, 1) * sp
    here = pltpu.roll(x, head_dim - half, 1) * sm
    if back:    # the other way round, rounded, then un-paired
        return _permuted((x * c - there - here).astype(dtype), p
                         ).astype(dtype)
    return (x * c + there + here).astype(dtype)


def _kernel(x_ref, c_ref, sp_ref, sm_ref, o_ref, *, head_dim, half, back):
    # once a trace of the body
    obs.count("kernels.traced.ff_rope_t" if back else "kernels.traced.ff_rope")
    rows, lanes = x_ref.shape[1:]
    sub = math.gcd(rows, _SUB_ROWS)
    p = _pairing(head_dim, half, back)

    def turn(i, carry):
        at = pl.ds(pl.multiple_of(i * sub, sub), sub)
        tables = c_ref[at, :], sp_ref[at, :], sm_ref[at, :]
        for head in range(lanes // head_dim):
            cols = slice(head * head_dim, (head + 1) * head_dim)
            o_ref[0, at, cols] = _turn_head(
                x_ref[0, at, cols], *tables, p, half=half, back=back,
                dtype=o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // sub, turn, 0)


def _call(x, c, sp, sm, *, head_dim, half, back, interpret):
    bsz, s, width = x.shape
    heads = width // head_dim
    # whole heads a step: the most that divide the heads within the lanes
    per_step = max(k for k in range(1, heads + 1)
                   if heads % k == 0 and k * head_dim <= _MAX_BLOCK_LANES)
    rows, lanes = min(_ROWS, s), per_step * head_dim
    table = pl.BlockSpec((rows, head_dim), lambda b, i, j: (i, 0))
    block = pl.BlockSpec((1, rows, lanes), lambda b, i, j: (b, i, j))
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))}
    return pl.pallas_call(
        functools.partial(_kernel, head_dim=head_dim, half=half, back=back),
        grid=(bsz, pl.cdiv(s, rows), width // lanes),
        in_specs=[block, table, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="ff_rope_t" if back else "ff_rope",
        **params,
    )(x, c, sp, sm)


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _make_rope(bsz: int, s: int, heads: int, head_dim: int, half: int,
               dtype: str, interpret):
    """The rotation of ``(bsz, s, heads * head_dim)`` as one custom-VJP
    function of (x, C, Sp, Sm).  Built once a configuration: each kernel
    body is traced once, here, and its equations bound wherever a layer,
    a recomputed block or a backward pass calls it (``traced_once``)."""
    x = jax.ShapeDtypeStruct((bsz, s, heads * head_dim), jnp.dtype(dtype))
    table = jax.ShapeDtypeStruct((s, head_dim), _F32)
    forward, backward = (
        traced_once(functools.partial(_call, head_dim=head_dim, half=half,
                                      back=back, interpret=interpret),
                    x, table, table, table)[0] for back in (False, True))

    @jax.custom_vjp
    def rope(x, c, sp, sm):
        return forward(x, c, sp, sm)[0]

    def rope_fwd(x, c, sp, sm):
        return rope(x, c, sp, sm), (c, sp, sm)

    def rope_bwd(tables, dy):
        # the tables are positions, not parameters
        return (backward(dy, *tables)[0], *map(jnp.zeros_like, tables))

    rope.defvjp(rope_fwd, rope_bwd)
    return rope


def rope_packed(x, cos, sin, heads: int, interpret=None):
    """``apply_rope(.., "split")`` of every head of x (B, S, heads *
    head_dim) where it lies: cos and sin (S, rotated / 2) float32 as
    ``rotary_table`` gives them -> the same shape and type.  The shapes
    must be the kernel's (:func:`fits` without the gate); a sequence
    shorter than a block of rows is padded to whole sublane tiles."""
    bsz, s, width = x.shape
    head_dim, half = width // heads, cos.shape[-1]
    if head_dim % LANES or heads * head_dim != width \
            or not 0 < 2 * half <= head_dim:
        raise ValueError(
            f"ff_rope: {heads} heads in {width} columns with {2 * half} "
            f"turned are not the kernel's shapes")
    pad = -s % 16 if s < _ROWS else 0
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        cos, sin = (jnp.pad(t, ((0, pad), (0, 0))) for t in (cos, sin))
    interpret = _should_interpret() if interpret is None else interpret
    rope = _make_rope(bsz, s + pad, heads, head_dim, half, x.dtype.name,
                      interpret)
    y = rope(x, *tables(cos.astype(_F32), sin.astype(_F32), head_dim))
    return y[:, :s] if pad else y
