"""Flash attention as Pallas TPU kernels (forward + custom-VJP backward).

The O(S^2) score matrix never leaves VMEM: the kernel streams K/V blocks
through the MXU against a resident Q block, maintaining the numerically
stable running max / denominator (same math as
parallel/ring_attention._stream_block, which is the XLA fallback path).
Backward is the standard flash recomputation: softmax probabilities are
rebuilt per tile from the saved log-sum-exp, so residual memory is O(S)
per row (out + lse) instead of O(S^2).

Layout.  The kernels work on the projections' own ``(B, S, H*hd)``
arrays, in the operands' type: a block is ``(block, W)`` with ``W`` a
multiple of 128 lanes holding ``G`` whole heads, so nothing is padded or
transposed in HBM for the usual head widths (:func:`_layout`):

  * hd divides 128 (32, 64) and H is a multiple of 128/hd: ``G = 128/hd``
    heads share one 128-lane block (``pack<G>``).  Head g's scores come
    from operands whose other lanes are zeroed, which costs the MXU what
    padding hd to 128 would, and its lanes of a W-wide product are
    selected afterwards;
  * hd a multiple of 128: one head a block (``pack1``);
  * anything else (hd 80, or H not a multiple of G): hd is zero-padded to
    the next multiple of 128 (``pad<hd_p>``), the one case that pads;
  * a value width of its own (latent attention: q, k 192 wide, v and the
    result 128): one head a block, each side rounded up to whole lanes by
    itself (``pad256v128``: q, k, dq, dk padded to 256, which costs the
    MXU nothing since it contracts 128 lanes at a time; v, out, do and dv
    stay 128 wide and nothing of theirs is padded).

Accumulators, scores, softmax, lse and delta are float32 in VMEM; out,
dq, dk and dv leave the kernels in the operands' type (the partial form
keeps a float32 out, since :func:`combine_partials` merges them).  lse and
delta are lane-dense ``(B, H, 1, S)`` rows.

Tiles.  grid = (B, H/G, outer blocks, inner blocks); both passes work on
transposed ``(block_k, block_q)`` score tiles, so the softmax statistics,
lse and delta are lane-dense rows that reduce and broadcast along
sublanes, a head's share of a W-row result is a sublane slice, and no
tile-sized operand is transposed.  Blocks are 1024 x 1024 (a shorter
sequence is one block; :func:`_pick_block`).  A causal tile wholly above
the diagonal is skipped and its operands are not fetched (the index maps
clamp to the nearest live block); a tile on the diagonal is walked in
square pieces (512 forward, 256 backward) of which those above the
diagonal are dropped and only those on it build a mask; the last K block
of a padded length builds one too, and no other tile does; ``1/sqrt(hd)``
is folded into the hoisted copy of q (forward) or k (backward).  The
backward is one kernel while what it keeps in VMEM fits
``_FUSED_BWD_BYTES`` (:func:`_fused_bwd_bytes`; ``fused``: K blocks
outer, Q inner, one recomputation of the probabilities a tile, dq^T of
the whole head group accumulated in float32 scratch, a Q block's written
into the resident dq block once no later K block adds to it) and two
beyond it (``split``: ``ff_flash_bwd_dkv`` and ``ff_flash_bwd_dq``, each
rebuilding the tile): S 16 384 at W 128 and S 8192 at W 256 are fused,
S 65 536 at W 256 is split.

Window.  Under ``window`` (causal self-attention) query ``i`` sees the
keys ``i - window < t <= i``.  The blocks are the largest of 1024, 512, 256,
128 that divides the window (512 at a window of 512), a tile wholly left
of every query's window in it is skipped exactly as one above the diagonal
is, and the inner grid axis walks only the band of blocks a block can meet
(two steps a block at a window of one block, where the causal call walks
sixteen at S 8192), the fused backward's too; the tile the window's
left edge cuts is walked in pieces like the one on the diagonal (128
forward, 256 backward), those below ITS diagonal dropped.  The kernels of
such a call are named ``ff_flash_win_*``.

Sequence lengths that are not a block multiple are zero-padded (padded K
columns masked, padded Q rows sliced off).  Which variant a call took is
counted once a trace in ``kernels.flash.<layout>.<backward>``, with
``.w<window>`` after it under a window.

On TPU the kernels compile via Mosaic; elsewhere they run in interpreter
mode, so the identical code path is exercised by the CPU test suite.

Measured on a v5e (PERF.md section 6, PR 27; b16 h12 s1024 d64 causal
bf16, ms forward / backward a call, 40 pipelined calls): XLA blockwise
4.94 / 7.39; the kernels this file held before (hd padded to 128, float32
results, (block_q, block_k) tiles with (block, 1) column statistics, two
backward kernels) 2.27 / 3.61; this layout with column statistics 1.40 /
1.36 at 512 x 512, and 64-lane blocks of a (B*H, S, 64) layout 1.91 / 2.04;
transposed tiles 0.71 / 1.26 at 512 x 512, 1.33 / 1.50 at 256 x 256, 0.59 /
1.38 at 1024 x 1024; with the diagonal walked in pieces 0.51 / 1.08 (pieces
of 512) and 0.81 / 0.93 (256), which is why the passes take different
pieces; inside the GPT-2 step 0.50 / 0.78.  The split backward costs 0.5-1.0
ms more than the fused one there (PERF.md section 6 has the two forms at
8192 and 16 384 positions).  The kernels beat XLA's blockwise attention
at every shape tried (S 512 to 8192, hd 64 and 128, bf16 and float32).

This is the framework's hand-written-kernel layer — the role the CUDA leaf
tasks play in the reference (e.g. conv_2d.cu:523-536), applied to the one
op family the reference lacks (attention, SURVEY.md §2.6) where manual VMEM
scheduling beats XLA.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu import obs

LANES = 128
_NEG_INF = float("-inf")
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# what the fused backward may count (:func:`_fused_bwd_bytes`), a quarter
# of the limit left for what Mosaic holds beyond the count
_FUSED_BWD_BYTES = _VMEM_LIMIT_BYTES * 3 // 4
# score tiles, and the pieces a tile on the diagonal is walked in: sizes
# picked on the v5e (PERF.md section 6, PR 27)
_BLOCK = 1024
_FWD_PIECE = 512
_BWD_PIECE = 256
# under a window: the blocks tried, largest first, and the pieces the two
# tiles the mask cuts (diagonal and left edge) are walked in, forward and
# backward (PERF.md section 6, PR 34: at 72 heads under a window of 512
# the forward read 6.28 ms in pieces of 128, 7.60 in 256, 7.08 whole; the
# backward 27.6, 25.2, 26.9; blocks of 256 lost both, 12.4 and 33.1)
_WINDOW_BLOCKS = (1024, 512, 256, 128)
_WINDOW_FWD_PIECE = 128
_WINDOW_BWD_PIECE = 256
_F32 = jnp.float32
# the forward kernel's two results by name: a recomputed block keeps them
# (FFModel._run_recomputed) instead of running the kernel once more for
# the backward kernels, which read both; out and lse are 68 MB a layer of
# the Moonlight cell against 6.84 ms of ff_flash_fwd (PERF.md section 6,
# PR 29).  Outside a jax.checkpoint a name is an identity
KEPT_RESULTS = ("ff_flash_out", "ff_flash_lse")


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _layout(h: int, hd: int, hdv: int):
    """(G heads a block, query/key and value head widths in the kernel's
    arrays, name).  A value width of its own (latent attention: 192
    against 128) takes one head a block, each width rounded up to whole
    lanes by itself, so the narrower side is not padded to the wider."""
    if hdv != hd:
        hd_p, hdv_p = _round_up(hd, LANES), _round_up(hdv, LANES)
        return 1, hd_p, hdv_p, (
            f"{'pack' if hd_p == hd else 'pad'}{hd_p}"
            f"{'v' if hdv_p == hdv else 'vpad'}{hdv_p}")
    g = LANES // hd if LANES % hd == 0 else 0
    if g >= 1 and h % g == 0:
        return g, hd, hd, f"pack{g}"
    if hd % LANES == 0:
        return 1, hd, hd, "pack1"
    hd_p = _round_up(hd, LANES)
    return 1, hd_p, hd_p, f"pad{hd_p}"


def _pick_block(s: int, interpret: bool, window: int = None) -> int:
    """Score-tile size along a sequence of length s.  A short sequence is
    one block of its own length (8-aligned in interpret mode, 128-aligned
    on hardware); a long one takes the block that pads it least.  Under a
    window shorter than the sequence the block is the largest that
    divides the window (512 at a window of 512): the tile the window's
    left edge cuts is then square like the one on the diagonal and is
    walked in the same pieces, and no tile is wider than the window."""
    if window is not None and window < s:
        fits = [b for b in _WINDOW_BLOCKS if window % b == 0]
        if fits:
            return fits[0]
    one = _round_up(s, 8 if interpret else LANES)
    if one <= _BLOCK:
        return one
    return min((_BLOCK, 3 * _BLOCK // 4, _BLOCK // 2),
               key=lambda b: _round_up(s, b))


def _nt(a, b):
    """a (m, c) x b (n, c)^T -> (m, n) float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=_F32)


def _nn(a, b):
    """a (m, c) x b (c, n) -> (m, n) float32."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=_F32)


def _only_head(x, g: int, heads: int, hd: int):
    """x with the lanes of every head but g zeroed."""
    if heads == 1:
        return x
    i = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((i >= g * hd) & (i < (g + 1) * hd), x, 0.0)


def _by_head(parts, hd: int):
    """One array whose head-g lanes come from ``parts[g]``."""
    out = parts[0]
    if len(parts) > 1:
        i = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for g in range(1, len(parts)):
            out = jnp.where(i >= g * hd, parts[g], out)
    return out


class _Tiles:
    """Static geometry of one call's score tiles: which are live (causal:
    not wholly above the diagonal; under a ``window``: not wholly left of
    every query's window either), which need a mask (straddle the
    diagonal or the window's left edge, or hold padded K columns), and how
    a tile on the diagonal of square blocks splits into ``piece``-sized
    squares of which those above the diagonal are dropped.

    Under a window (query ``i`` sees the keys ``i - window < t <= i``;
    self-attention only) the tile the left edge cuts, ``window / block``
    blocks left of the diagonal one, splits the same way with the pieces
    below ITS diagonal dropped, and the inner grid axis of every kernel
    walks only the blocks some query of the outer block can see
    (``inner_q``, ``inner_k`` steps; :meth:`q_at`, :meth:`k_at` give the
    block of a step), so the tiles the window leaves out cost no grid
    step.  Without a window the inner axis is every block, as it was."""

    def __init__(self, causal, sk, block_q, block_k, n_q, n_k, piece,
                 window=None):
        self.causal, self.sk = causal, sk
        self.bq, self.bk, self.n_q, self.n_k = block_q, block_k, n_q, n_k
        self.k_padded = sk % block_k != 0
        self.window = window
        square = causal and block_q == block_k and block_q % piece == 0
        if window is not None:
            square = square and window % block_q == 0
        self.n_sub = block_q // piece if square else 1
        self.inner_q, self.inner_k = n_q, n_k
        if window is not None:
            self.inner_k = max(
                self._last_k(q, min) - self._first_k(q, max) + 1
                for q in range(n_q))
            self.inner_q = max(
                self._last_q(k, min) - self._first_q(k, min) + 1
                for k in range(n_k))

    # the first and last block of one side that a block of the other side
    # meets, on Python ints (mn, mx = min, max) and on grid indices alike
    def _first_q(self, ki, mn):
        return mn((ki * self.bk) // self.bq, self.n_q - 1)

    def _last_q(self, ki, mn):
        return mn((ki * self.bk + self.bk + self.window - 2) // self.bq,
                  self.n_q - 1)

    def _first_k(self, qi, mx):
        return mx((qi * self.bq - self.window + 1) // self.bk, 0)

    def _last_k(self, qi, mn):
        return mn((qi * self.bq + self.bq - 1) // self.bk, self.n_k - 1)

    def q_at(self, ki, j):
        """The Q block of inner step ``j`` under K block ``ki``; past the
        last one the window lets ``ki`` meet it names no live tile."""
        return self._first_q(ki, jnp.minimum) + j \
            if self.window is not None else j

    def k_at(self, qi, j):
        return self._first_k(qi, jnp.maximum) + j \
            if self.window is not None else j

    def live(self, qi, ki):
        if not self.causal:
            return True
        seen = qi * self.bq + self.bq - 1 >= ki * self.bk
        if self.window is None:
            return seen
        # the nearest pair of the tile lies inside the window
        near = qi * self.bq - (ki * self.bk + self.bk - 1) < self.window
        inside = jnp.logical_and(qi < self.n_q, ki < self.n_k)
        return jnp.logical_and(jnp.logical_and(seen, near), inside)

    def masked(self, qi, ki):
        """None when no tile of the call needs a mask."""
        m = None
        if self.causal:  # some column of the tile lies right of some row
            m = ki * self.bk + self.bk - 1 > qi * self.bq
        if self.window is not None:  # its farthest pair leaves the window
            far = qi * self.bq + self.bq - 1 - ki * self.bk >= self.window
            m = jnp.logical_or(m, far)
        if self.k_padded:
            last = ki == self.n_k - 1
            m = last if m is None else jnp.logical_or(m, last)
        return m

    def valid(self, qi, ki, qs: slice, ks: slice, edges=True):
        """Mask of the (k piece, q piece) of transposed tile (qi, ki).
        ``edges`` names the one edge a piece is known to meet
        (``"causal"``, ``"window"``); True: every edge the call has."""
        shape = (ks.stop - ks.start, qs.stop - qs.start)
        kpos = ki * self.bk + ks.start + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0)
        ok = kpos < self.sk if self.k_padded else None
        if self.causal:
            qpos = qi * self.bq + qs.start + jax.lax.broadcasted_iota(
                jnp.int32, shape, 1)
            if edges is True or edges == "causal":
                ok = qpos >= kpos if ok is None else ok & (qpos >= kpos)
            if self.window is not None and (edges is True
                                            or edges == "window"):
                near = qpos - kpos < self.window
                ok = near if ok is None else ok & near
        return ok

    def first_live_q(self, ki):
        return self._first_q(ki, jnp.minimum) if self.causal else 0

    def last_live_k(self, qi):
        return self._last_k(qi, jnp.minimum) if self.causal \
            else self.n_k - 1

    def q_block(self, ki, j):
        """The Q block to fetch at inner step ``j``: a live one, so a
        dead step moves nothing."""
        if self.window is None:
            return jnp.maximum(j, self.first_live_q(ki))
        return jnp.clip(self.q_at(ki, j), self.first_live_q(ki),
                        self._last_q(ki, jnp.minimum))

    def k_block(self, qi, j):
        if self.window is None:
            return jnp.minimum(j, self.last_live_k(qi))
        return jnp.clip(self.k_at(qi, j), self._first_k(qi, jnp.maximum),
                        self.last_live_k(qi))

    def run(self, qi, ki, body):
        """Call ``body(pieces)`` under the grid-step conditions it needs;
        ``pieces`` is a static list of (q slice, k slice, masked), where
        ``masked`` is False, True or the one edge to mask for."""
        whole = (slice(0, self.bq), slice(0, self.bk))
        live, masked = self.live(qi, ki), self.masked(qi, ki)
        if self.window is not None:
            return self._run_windowed(qi, ki, body, whole, live, masked)
        if masked is None:
            pl.when(live)(lambda: body([(*whole, False)]))
            return
        pl.when(jnp.logical_and(live, jnp.logical_not(masked)))(
            lambda: body([(*whole, False)]))
        if self.n_sub > 1:
            # square causal blocks: the straddling tiles are qi == ki
            step = self.bq // self.n_sub
            cut = [slice(i * step, (i + 1) * step)
                   for i in range(self.n_sub)]
            pl.when(qi == ki)(lambda: body(
                [(cut[a], cut[c], c == a or self.k_padded)
                 for a in range(self.n_sub) for c in range(a + 1)]))
            if not self.k_padded:
                return
            masked = jnp.logical_and(masked, qi != ki)
        pl.when(jnp.logical_and(live, masked))(
            lambda: body([(*whole, True)]))

    def _run_windowed(self, qi, ki, body, whole, live, masked):
        if self.n_sub == 1:     # blocks the window is no multiple of
            pl.when(jnp.logical_and(live, jnp.logical_not(masked)))(
                lambda: body([(*whole, False)]))
            pl.when(jnp.logical_and(live, masked))(
                lambda: body([(*whole, True)]))
            return
        # square blocks, the window a whole number of them: the diagonal
        # tile keeps the pieces on and below its diagonal, the tile
        # window / block to its left those on and above (a key there is
        # seen when its place in the tile is past the query's), and the
        # tiles between them are seen whole
        step = self.bq // self.n_sub
        cut = [slice(i * step, (i + 1) * step) for i in range(self.n_sub)]
        edge = qi - self.window // self.bq

        def pieces(cols, what):
            return [(cut[a], cut[c], (True if self.k_padded else what)
                     if c == a else self.k_padded)
                    for a in range(self.n_sub) for c in cols(a)]

        pl.when(jnp.logical_and(live, ki == qi))(lambda: body(
            pieces(lambda a: range(a + 1), "causal")))
        pl.when(jnp.logical_and(live, ki == edge))(lambda: body(
            pieces(lambda a: range(a, self.n_sub), "window")))
        pl.when(jnp.logical_and(live, jnp.logical_and(
            ki != qi, ki != edge)))(lambda: body([(*whole, False)]))


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary",
                             "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)}


# ---------------------------------------------------------------------------
# Both passes work on transposed (block_k, block_q) score tiles: the
# softmax statistics, lse and delta are then lane-dense (1, block_q) rows
# that reduce and broadcast along sublanes, a head's share of a W-row
# result is a sublane slice, and no tile-sized operand is ever transposed.


def _head_rows(g: int, hd: int):
    return slice(g * hd, (g + 1) * hd)


def _transposed(x, dtype):
    """x^T through float32, the one type every Mosaic transposes."""
    return x.astype(_F32).T.astype(dtype)


# forward: Q blocks outer, K blocks inner


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, qs_scr, m_scr, l_scr,
                acc_scr, *, t: _Tiles, scale, heads, hd, hdv, name):
    # a kernel's body is Python that runs once each time JAX traces it:
    # ``kernels.traced.<kernel>`` beside the counters of traced calls
    # says how many call sites share a trace
    obs.count("kernels.traced." + name)
    qi, jk = pl.program_id(2), pl.program_id(3)
    ki = t.k_at(qi, jk)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)
        q = q_ref[0].astype(_F32) * scale
        for g in range(heads):
            qs_scr[g] = _only_head(q, g, heads, hd).astype(qs_scr.dtype)

    def tile(pieces):
        v_t = _transposed(v_ref[0], v_ref.dtype)              # (W, block_k)
        for qs, ks, masked in pieces:
            k = k_ref[0, ks, :]
            valid = t.valid(qi, ki, qs, ks, masked) if masked else None
            for g in range(heads):
                rows = _head_rows(g, hdv)
                s_t = _nt(k, qs_scr[g, qs, :])
                if masked:
                    s_t = jnp.where(valid, s_t, _NEG_INF)
                # K block 0 comes first and holds a visible column for
                # every query, so m is finite from the first piece on
                m_prev = m_scr[g, :, qs]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s_t, axis=0, keepdims=True))
                m_ref = m_new
                if t.window is not None:
                    # the first piece a query meets under a window may
                    # hold none of its keys: the piece then adds nothing
                    m_ref = jnp.where(m_new == _NEG_INF, 0.0, m_new)
                p_t = jnp.exp(s_t - m_ref)
                corr = jnp.exp(m_prev - m_ref)
                l_scr[g, :, qs] = l_scr[g, :, qs] * corr + jnp.sum(
                    p_t, axis=0, keepdims=True)
                acc_scr[rows, qs] = acc_scr[rows, qs] * corr + _nn(
                    v_t[rows, ks], p_t.astype(v_t.dtype))
                m_scr[g, :, qs] = m_new

    t.run(qi, ki, tile)

    @pl.when(jk == t.inner_k - 1)
    def _finish():
        for g in range(heads):
            rows = _head_rows(g, hdv)
            if t.window is None:
                acc_scr[rows, :] = acc_scr[rows, :] / l_scr[g]
                lse_ref[0, g] = m_scr[g] + jnp.log(l_scr[g])
                continue
            # a padded query row far past the last key sees none: its
            # result is 0 and its lse finite
            seen = l_scr[g] > 0.0
            l = jnp.where(seen, l_scr[g], 1.0)
            acc_scr[rows, :] = acc_scr[rows, :] / l
            lse_ref[0, g] = jnp.where(seen, m_scr[g], 0.0) + jnp.log(l)
        o_ref[0] = acc_scr[...].T.astype(o_ref.dtype)


def _fwd_call(q, k, v, *, t: _Tiles, heads, hd, hdv, scale, out_dtype,
              interpret, name="ff_flash_"):
    b, sq, width = q.shape
    w, wv = heads * hd, heads * hdv
    groups = width // w
    bq, bk = t.bq, t.bk

    def kv_spec(width_):
        return pl.BlockSpec(
            (1, bk, width_), lambda b_, j, qi, ki: (b_, t.k_block(qi, ki),
                                                    j))

    return pl.pallas_call(
        functools.partial(_fwd_kernel, t=t, scale=scale, heads=heads, hd=hd,
                          hdv=hdv, name=name + "fwd"),
        grid=(b, groups, t.n_q, t.inner_k),
        in_specs=[pl.BlockSpec((1, bq, w), lambda b_, j, qi, ki: (b_, qi, j)),
                  kv_spec(w), kv_spec(wv)],
        out_specs=[
            pl.BlockSpec((1, bq, wv), lambda b_, j, qi, ki: (b_, qi, j)),
            pl.BlockSpec((1, heads, 1, bq),
                         lambda b_, j, qi, ki: (b_, j, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, groups * wv), out_dtype),
            jax.ShapeDtypeStruct((b, groups * heads, 1, sq), _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, bq, w), q.dtype),
            pltpu.VMEM((heads, 1, bq), _F32),
            pltpu.VMEM((heads, 1, bq), _F32),
            pltpu.VMEM((wv, bq), _F32),
        ],
        interpret=interpret,
        name=name + "fwd",
        **_params(interpret),
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: p^T is rebuilt from the saved lse; delta = rowsum(do * o) (less
# the lse cotangent)


def _p_ds_t(ks, vm, q, do, lse, delta, valid):
    """p^T and ds^T of one head's tile.  ``ks`` is k scaled and ``vm`` is
    v, both with the other heads' lanes zeroed; lse, delta: (1, block_q)."""
    p_t = jnp.exp(_nt(ks, q) - lse)
    if valid is not None:
        p_t = jnp.where(valid, p_t, 0.0)
    return p_t, p_t * (_nt(vm, do) - delta)


def _hoist_kv(k_ref, v_ref, ks_scr, vm_scr, kst_scr, scale, heads, hd, hdv):
    """This K block's operands: per head k scaled and v, each with the
    other heads' lanes zeroed, and (k scaled)^T, whose rows split by head."""
    k = k_ref[0].astype(_F32) * scale
    v = v_ref[0].astype(_F32)
    for g in range(heads):
        ks_scr[g] = _only_head(k, g, heads, hd).astype(ks_scr.dtype)
        vm_scr[g] = _only_head(v, g, heads, hdv).astype(vm_scr.dtype)
    if kst_scr is not None:
        kst_scr[...] = k.T.astype(kst_scr.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                t: _Tiles, scale, heads, hd, hdv, with_dq, name):
    """K blocks outer, Q blocks inner: dk, dv accumulate over the inner
    loop; with ``with_dq`` (the fused form) dq^T of the whole head group
    accumulates across both loops, a Q block's leaving with the last K
    block that sees it."""
    obs.count("kernels.traced." + name)
    if with_dq:
        (dq_ref, dk_ref, dv_ref,
         ks_scr, vm_scr, dk_scr, dv_scr, kst_scr, dqt_scr) = rest
    else:
        dk_ref, dv_ref, ks_scr, vm_scr, dk_scr, dv_scr = rest
        kst_scr = dqt_scr = None
    ki, jq = pl.program_id(2), pl.program_id(3)
    qi = t.q_at(ki, jq)

    @pl.when(jq == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, dk_scr.dtype)
        dv_scr[...] = jnp.zeros(dv_scr.shape, dv_scr.dtype)
        _hoist_kv(k_ref, v_ref, ks_scr, vm_scr, kst_scr, scale, heads, hd,
                  hdv)

    if with_dq:
        # dq^T of Q block qi sums over the K blocks that see it: zeroed at
        # the first, written at the last.  Under a window the inner axis
        # walks the band, so those are the window's left edge and the
        # diagonal, and a step past the last Q block names none
        first = ki == 0
        if t.window is not None:
            first = jnp.logical_and(qi < t.n_q,
                                    ki == t._first_k(qi, jnp.maximum))

        @pl.when(first)
        def _init_dq():
            dqt_scr[qi] = jnp.zeros(dqt_scr.shape[1:], dqt_scr.dtype)

    def tile(pieces):
        for qs, ks, masked in pieces:
            q, do = q_ref[0, qs, :], do_ref[0, qs, :]
            valid = t.valid(qi, ki, qs, ks, masked) if masked else None
            dvs, dks = [], []
            for g in range(heads):
                p_t, ds_t = _p_ds_t(ks_scr[g, ks, :], vm_scr[g, ks, :], q,
                                    do, lse_ref[0, g, :, qs],
                                    delta_ref[0, g, :, qs], valid)
                ds_t = ds_t.astype(q.dtype)
                dvs.append(_nn(p_t.astype(do.dtype), do))
                dks.append(_nn(ds_t, q))
                if with_dq:
                    rows = _head_rows(g, hd)
                    dqt_scr[qi, rows, qs] += _nn(kst_scr[rows, ks], ds_t)
            dv_scr[ks, :] += _by_head(dvs, hdv)
            dk_scr[ks, :] += _by_head(dks, hd)

    t.run(qi, ki, tile)

    @pl.when(jq == t.inner_q - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    if with_dq:
        last = ki == t.n_k - 1
        if t.window is not None:
            last = jnp.logical_and(qi < t.n_q, ki == t.last_live_k(qi))

        @pl.when(last)
        def _finish_dq():
            rows = pl.ds(pl.multiple_of(qi * t.bq, t.bq), t.bq)
            dq_ref[0, rows, :] = dqt_scr[qi].T.astype(dq_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   ks_scr, vm_scr, kst_scr, dqt_scr,
                   *, t: _Tiles, scale, heads, hd, hdv, name):
    """Q blocks outer, K blocks inner (the split form's second kernel)."""
    obs.count("kernels.traced." + name)
    qi, jk = pl.program_id(2), pl.program_id(3)
    ki = t.k_at(qi, jk)

    @pl.when(jk == 0)
    def _init():
        dqt_scr[...] = jnp.zeros(dqt_scr.shape, dqt_scr.dtype)

    def tile(pieces):
        _hoist_kv(k_ref, v_ref, ks_scr, vm_scr, kst_scr, scale, heads, hd,
                  hdv)
        for qs, ks, masked in pieces:
            q, do = q_ref[0, qs, :], do_ref[0, qs, :]
            valid = t.valid(qi, ki, qs, ks, masked) if masked else None
            for g in range(heads):
                rows = _head_rows(g, hd)
                _, ds_t = _p_ds_t(ks_scr[g, ks, :], vm_scr[g, ks, :], q, do,
                                  lse_ref[0, g, :, qs],
                                  delta_ref[0, g, :, qs], valid)
                dqt_scr[rows, qs] += _nn(kst_scr[rows, ks],
                                         ds_t.astype(q.dtype))

    t.run(qi, ki, tile)

    @pl.when(jk == t.inner_k - 1)
    def _finish():
        dq_ref[0] = dqt_scr[...].T.astype(dq_ref.dtype)


def _fused_bwd_bytes(bq, bk, n_q, heads, hd, hdv, itemsize):
    """VMEM the fused backward asks for: the pipeline's two copies of the
    q, do, k and v blocks, of the lse and delta rows (a row a head at
    eight sublanes) and of the dk, dv and whole-sequence dq blocks; the
    hoisted k and v per head and (k scaled)^T; dk, dv and dq^T of every Q
    block in float32; two float32 score tiles alive at once.  Compiles
    for a described v5e took this or up to 8.25 MiB less (29.5 of 31.4
    MiB at 2 x 8192 x 16 heads of 192 / 128, 26.75 of 28.9 at 16 384 x 28
    of 128)."""
    w, wv = heads * hd, heads * hdv
    blocks = 2 * (bq * (w + wv) + 2 * bk * (w + wv) + n_q * bq * w) \
        * itemsize
    rows = 2 * 2 * heads * 8 * bq * 4
    scratch = (heads * bk * (w + wv) + w * bk) * itemsize \
        + (bk * (w + wv) + n_q * w * bq) * 4
    return blocks + rows + scratch + 2 * bk * bq * 4


def _bwd_call(q, k, v, do, lse, delta, *, t: _Tiles, heads, hd, hdv, scale,
              fused, interpret, name="ff_flash_"):
    b, sq, width = q.shape
    w, wv = heads * hd, heads * hdv
    groups = width // w
    bq, bk = t.bq, t.bk
    common = dict(t=t, scale=scale, heads=heads, hd=hd, hdv=hdv)
    first = name + ("bwd" if fused else "bwd_dkv")

    # K blocks outer, Q blocks inner
    def q_spec(width_):
        return pl.BlockSpec(
            (1, bq, width_), lambda b_, j, ki, qi: (b_, t.q_block(ki, qi),
                                                    j))

    row_spec = pl.BlockSpec(
        (1, heads, 1, bq), lambda b_, j, ki, qi: (b_, j, 0,
                                                  t.q_block(ki, qi)))

    def kv_spec(width_):
        return pl.BlockSpec((1, bk, width_),
                            lambda b_, j, ki, qi: (b_, ki, j))

    kv_scratch = [pltpu.VMEM((heads, bk, w), q.dtype),
                  pltpu.VMEM((heads, bk, wv), q.dtype)]
    acc_scratch = [pltpu.VMEM((bk, w), _F32), pltpu.VMEM((bk, wv), _F32)]
    dq_scratch = [pltpu.VMEM((w, bk), q.dtype)]
    out_specs = [kv_spec(w), kv_spec(wv)]
    out_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    scratch = kv_scratch + acc_scratch
    if fused:
        out_specs.insert(0, pl.BlockSpec(
            (1, sq, w), lambda b_, j, ki, qi: (b_, 0, j)))
        out_shape.insert(0, jax.ShapeDtypeStruct(q.shape, q.dtype))
        scratch += dq_scratch + [pltpu.VMEM((t.n_q, w, bq), _F32)]
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, with_dq=fused, name=first, **common),
        grid=(b, groups, t.n_k, t.inner_q),
        in_specs=[q_spec(w), kv_spec(w), kv_spec(wv), q_spec(wv), row_spec,
                  row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name=first,
        **_params(interpret),
    )(q, k, v, do, lse, delta)
    if fused:
        return outs

    # Q blocks outer, K blocks inner
    def q_spec(width_):
        return pl.BlockSpec((1, bq, width_),
                            lambda b_, j, qi, ki: (b_, qi, j))

    row_spec = pl.BlockSpec((1, heads, 1, bq),
                            lambda b_, j, qi, ki: (b_, j, 0, qi))

    def kv_spec(width_):
        return pl.BlockSpec(
            (1, bk, width_), lambda b_, j, qi, ki: (b_, t.k_block(qi, ki),
                                                    j))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, name=name + "bwd_dq", **common),
        grid=(b, groups, t.n_q, t.inner_k),
        in_specs=[q_spec(w), kv_spec(w), kv_spec(wv), q_spec(wv), row_spec,
                  row_spec],
        out_specs=q_spec(w),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=kv_scratch + dq_scratch + [pltpu.VMEM((w, bq), _F32)],
        interpret=interpret,
        name=name + "bwd_dq",
        **_params(interpret),
    )(q, k, v, do, lse, delta)
    return (dq, *outs)


# ---------------------------------------------------------------------------
# public op, differentiable.  ``packed``: (B, S, H*hd) in and out;
# otherwise (B, H, S, hd) in and out


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _make_flash(q_shape, k_shape, dv, qdt, kdt, vdt, causal, block_q,
                block_k, interpret, with_lse=False, packed=False,
                scale=None, window=None):
    """Build a custom-VJP flash op specialized for one static configuration
    (shapes/dtypes/blocks are Python constants closed over by the kernels;
    the VJP residuals are pure arrays), and name its variant.  ``q_shape``
    and ``k_shape`` are (B, H, S, hd) whatever the arrays' layout; ``dv``
    is the value's head width (v, the result and their gradients), which
    latent attention sets apart from the query/key width; ``scale`` is
    what the scores are multiplied by, ``1/sqrt(hd)`` unless a model
    gives its own number; under ``window`` (causal self-attention only)
    query ``i`` sees the ``window`` keys ``i - window < t <= i`` and the
    kernels are named ``ff_flash_win_*``.  A window no shorter than the
    sequence leaves nothing out and is the causal call.

    With ``with_lse`` the op returns ``(out, lse)`` — the *partial*
    attention form used by ring/context parallelism, where per-chunk
    results are merged by log-sum-exp weighting.  The lse cotangent folds
    into the backward kernels for free: d lse/d s_ij = p_ij, so passing
    ``delta - g_lse`` where the kernels expect ``delta`` yields
    ds = p (dp - delta + g_lse) — no kernel changes."""
    b, h, sq, d = q_shape
    sk = k_shape[2]
    scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
    heads, hd, hdv, layout = _layout(h, d, dv)
    if window is not None:
        if not causal or sq != sk or with_lse or window < 1:
            raise ValueError(
                f"a window ({window}) is for causal self-attention of one "
                f"piece: causal {causal}, {sq} queries on {sk} keys")
        if window >= sq:
            window = None
    bq = (_pick_block(sq, interpret, window) if block_q is None
          else min(block_q, _round_up(sq, 8)))
    bk = (_pick_block(sk, interpret, window) if block_k is None
          else min(block_k, _round_up(sk, 8)))
    sq_p, sk_p = _round_up(sq, bq), _round_up(sk, bk)
    itemsize = max(jnp.dtype(x).itemsize for x in (qdt, kdt, vdt))
    fused = _fused_bwd_bytes(bq, bk, sq_p // bq, heads, hd, hdv,
                             itemsize) <= _FUSED_BWD_BYTES
    variant = f"{layout}.{'fused' if fused else 'split'}"
    names = {}
    if window is None:
        fwd_tiles, bwd_tiles = (
            _Tiles(causal, sk, bq, bk, sq_p // bq, sk_p // bk, piece)
            for piece in (_FWD_PIECE, _BWD_PIECE))
    else:
        fwd_tiles, bwd_tiles = (
            _Tiles(causal, sk, bq, bk, sq_p // bq, sk_p // bk, piece,
                   window)
            for piece in (_WINDOW_FWD_PIECE, _WINDOW_BWD_PIECE))
        variant += f".w{window}"
        names = {"name": "ff_flash_win_"}
    out_dtype = jnp.float32 if with_lse else qdt
    # inline jits: a model's layers share one trace of each kernel body
    # (tracing them is most of what a call costs before it compiles),
    # while every call site keeps its own operator name in the program
    fwd_call = jax.jit(functools.partial(
        _fwd_call, t=fwd_tiles, heads=heads, hd=hd, hdv=hdv, scale=scale,
        out_dtype=out_dtype, interpret=interpret, **names), inline=True)
    bwd_call = jax.jit(functools.partial(
        _bwd_call, t=bwd_tiles, heads=heads, hd=hd, hdv=hdv, scale=scale,
        fused=fused, interpret=interpret, **names), inline=True)

    def prep(x, s_p, d=d, hd=hd):
        """-> (B, S_pad, H*hd_kernel); zero head columns do not change
        scores, padded K rows are masked via sk, padded Q rows sliced off"""
        s = x.shape[1] if packed else x.shape[2]
        if not packed:
            x = x.transpose(0, 2, 1, 3)
        if hd != d:
            x = jnp.pad(x.reshape(b, s, h, d),
                        ((0, 0), (0, 0), (0, 0), (0, hd - d)))
        x = x.reshape(b, s, h * hd)
        if s_p != s:
            x = jnp.pad(x, ((0, 0), (0, s_p - s), (0, 0)))
        return x

    def unprep(x, s, d=d, hd=hd):
        """The inverse of prep for a kernel result of true length s."""
        if x.shape[1] != s:
            x = x[:, :s]
        if hd != d:
            x = x.reshape(b, s, h, hd)[..., :d]
        if packed:
            return x.reshape(b, s, h * d)
        return x.reshape(b, s, h, d).transpose(0, 2, 1, 3)

    prep_v = functools.partial(prep, d=dv, hd=hdv)
    unprep_v = functools.partial(unprep, d=dv, hd=hdv)

    def rows(x):
        """(B, H, Sq) float32 -> the kernels' (B, H, 1, Sq_pad)."""
        x = x.astype(jnp.float32).reshape(b, h, 1, sq)
        return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, sq_p - sq)))

    def run_fwd(q, k, v):
        qp, kp, vp = prep(q, sq_p), prep(k, sk_p), prep_v(v, sk_p)
        out, lse = map(checkpoint_name, fwd_call(qp, kp, vp), KEPT_RESULTS)
        # the named arrays are both what the caller gets and what the
        # backward holds, so one kept array serves both
        return unprep_v(out, sq), lse, (qp, kp, vp, lse, out)

    def run_bwd(res, g, g_lse=None):
        qp, kp, vp, lse, out = res
        do = prep_v(g, sq_p)
        # delta is zero on padded Q rows (do = 0 there), so they contribute
        # nothing to dk/dv even though their lse is arbitrary
        delta = jnp.einsum("bshd,bshd->bhs",
                           do.reshape(b, sq_p, h, hdv).astype(jnp.float32),
                           out.reshape(b, sq_p, h, hdv).astype(jnp.float32)
                           )[:, :, None, :]
        if g_lse is not None:
            delta = delta - rows(g_lse)  # ds = p (dp - delta + g_lse)
        dq, dk, dv = bwd_call(qp, kp, vp, do.astype(qdt), lse, delta)
        return (unprep(dq, sq).astype(qdt), unprep(dk, sk).astype(kdt),
                unprep_v(dv, sk).astype(vdt))

    if not with_lse:

        @jax.custom_vjp
        def flash(q, k, v):
            return run_fwd(q, k, v)[0]

        def flash_fwd(q, k, v):
            out, _, res = run_fwd(q, k, v)
            return out, res

        flash.defvjp(flash_fwd, run_bwd)
        return flash, variant

    def unpack(out, lse):
        return out, lse[:, :, 0, :sq]

    @jax.custom_vjp
    def flash_p(q, k, v):
        out, lse, _ = run_fwd(q, k, v)
        return unpack(out, lse)

    def flash_p_fwd(q, k, v):
        out, lse, res = run_fwd(q, k, v)
        return unpack(out, lse), res

    def flash_p_bwd(res, gs):
        return run_bwd(res, *gs)

    flash_p.defvjp(flash_p_fwd, flash_p_bwd)
    return flash_p, variant


def _call(q, k, v, q_shape, k_shape, dv, causal, block_q, block_k,
          interpret, **form):
    interpret = _should_interpret() if interpret is None else interpret
    f, variant = _make_flash(q_shape, k_shape, dv, q.dtype.name,
                             k.dtype.name, v.dtype.name, bool(causal),
                             block_q, block_k, interpret, **form)
    obs.count(f"kernels.flash.{variant}")
    return f(q, k, v)


def flash_attention(q, k, v, causal=False, block_q=None, block_k=None,
                    interpret=None, window=None):
    """softmax(q kᵀ / sqrt(d) [+ causal mask]) v without materializing the
    score matrix.  q, k: (B, H, S, d) and v: (B, H, S, dv); returns
    (B, H, Sq, dv) in q's type.  Blocks default to what the shapes
    select.  Under ``window`` a query sees itself and the ``window - 1``
    keys before it."""
    return _call(q, k, v, tuple(q.shape), tuple(k.shape), v.shape[-1],
                 causal, block_q, block_k, interpret, window=window)


def flash_attention_packed(q, k, v, num_heads, causal=False, block_q=None,
                           block_k=None, interpret=None, scale=None,
                           window=None):
    """:func:`flash_attention` on the projections' own layout: q and k are
    (B, S, H*d) with head h in columns ``h*d:(h+1)*d``, v is (B, S, H*dv)
    and so is the result — no (B,S,H,d) <-> (B,H,S,d) transpose on either
    side.  ``scale`` multiplies the scores in place of ``1/sqrt(d)``;
    ``window`` as :func:`flash_attention`'s."""
    def bhsd(x):
        b, s, width = x.shape
        return (b, num_heads, s, width // num_heads)

    return _call(q, k, v, bhsd(q), bhsd(k), v.shape[-1] // num_heads, causal,
                 block_q, block_k, interpret, packed=True, scale=scale,
                 window=window)


def flash_attention_partial(q, k, v, causal=False, block_q=None,
                            block_k=None, interpret=None):
    """Partial attention over one K/V chunk: returns ``(out, lse)`` where
    ``out`` (float32) is the chunk-normalized attention and ``lse``
    (B, H, Sq) the log-sum-exp of its scores.  Chunks merge exactly via
    :func:`combine_partials` — the building block of the Pallas ring-
    attention path (each ring step attends Q against the resident K/V
    block, then results merge by lse weight).  Differentiable in both
    outputs."""
    return _call(q, k, v, tuple(q.shape), tuple(k.shape), v.shape[-1],
                 causal, block_q, block_k, interpret, with_lse=True)


def combine_partials(o1, lse1, o2, lse2):
    """Merge two chunk-normalized partial attentions by log-sum-exp weight:
    softmax over the union of their key sets.  Fully-masked partials
    (lse = -inf, o = 0) drop out; if both are masked the result is 0."""
    m = jnp.maximum(lse1, lse2)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.where(jnp.isfinite(lse1), jnp.exp(lse1 - safe_m), 0.0)
    w2 = jnp.where(jnp.isfinite(lse2), jnp.exp(lse2 - safe_m), 0.0)
    tot = w1 + w2
    lse = jnp.where(tot > 0, safe_m + jnp.log(jnp.maximum(tot, 1e-30)),
                    _NEG_INF)
    denom = jnp.maximum(tot, 1e-30)[..., None]
    o = (o1 * w1[..., None] + o2 * w2[..., None]) / denom
    return o, lse
