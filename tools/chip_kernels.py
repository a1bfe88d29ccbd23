"""Run every Pallas kernel through Mosaic at the shapes the models use.

    python tools/chip_kernels.py               # on the chip
    python tools/chip_kernels.py gmm           # the grouped products alone
    python tools/chip_kernels.py ce            # the fused head alone
    python tools/chip_kernels.py win           # the windowed flash alone
    python tools/chip_kernels.py scan          # the state-space scan alone
    python tools/chip_kernels.py rope          # the rotary positions alone
    python tools/chip_kernels.py route         # the held experts' row search

The CPU suite runs these kernels in interpret mode at test shapes, and the
compiled branch picks other block shapes (flash_attention._make_flash,
fused_ce._make_fused), so tier-1 says nothing about what Mosaic accepts.
On the chip each case is compiled (forward + backward), run, compared with
its plain-XLA reference on the same inputs, and timed on the host clock
around ``block_until_ready`` (median of 5 calls — indicative, not a
benchmark).  A case that fails to compile is reported with the compiler's
message and the run exits 1 after the other cases have been tried.

Cases (issue 21 section 4): flash attention fwd+bwd at the BERT-base
training shape, at the GPT-2 benchmark cell's own shape (b16 h12 s1024
d64 causal, bfloat16) and at S=8192 d=64 causal; fused projection+CE at 8k tokens
x 32k vocab and its vocab-TP partial form, and at the three token cells'
own shapes (16 384 tokens at d 768 / V 50 257, d 2048 / V 100 352, d 2048
/ V 20 480, and a quarter of the second as the partial form), where the
forward is timed alone as well and the XLA reference walks the tokens in
chunks of 2048 (its float32 logits would be 6.6 GB whole); the grouped products of a held
expert at the Moonlight cell's shape (24 576 buffer rows, 8 groups at
uneven loads that fill half of it, 2048 x 1408, bfloat16): each ``ff_gmm``
form against the ``jax.lax.ragged_dot`` call autodiff makes in its place,
at the tiles the shapes pick and at the candidates of ``GMM_TILES``, 20
pipelined calls each, and the whole gated feed-forward, forward and
backward, through the kernels and through ``ragged_dot``; the windowed
flash kernels at the Laguna cell's shapes (b2 s8192 d128, 72 heads under a
window of 512, bfloat16) against XLA's masked attention walking the
queries a window at a time, at the block and pieces the shapes pick and
at the candidates of ``WIN_TILES``, beside the 48-head full layer's
causal call (the check to run after a libtpu change); the state-space
scan at the Granite cell's shape (2 x 8192 tokens, 64 heads of 64, state
128, chunks of 256, bfloat16): ``y`` and the gradients of ``x``, ``B``,
``C``, ``delta``, ``A_log`` and ``D`` through ``ff_ssd_fwd`` and
``ff_ssd_bwd`` against ``ops/ssm.py: ssd_chunked``, both timed forward
and forward + backward (the interpreter cannot see a write in flight, and
the kernels carry a state from grid step to grid step); the rotary
positions of the Laguna cell's q and k (2 x 8192 tokens, 72, 48 and 8
heads of 128, all or 64 of them turned, bfloat16): ``ff_rope`` and
``ff_rope_t`` against ``ops/seq_gated.py: apply_rope`` on the 4-D view,
each side's gradient beside the float32 computation of it, both timed
alone and behind a sliding layer's q projection, and the kernel at the
candidates of ``ROPE_BLOCKS``; the held experts' row search at the three
expert cells' shapes (``ROUTE_SHAPES``: 131 072 running counts, 10 240 to
32 768 buffer rows): each method of ``jnp.searchsorted`` and
``expert_share.first_reaching`` at each of ``ROUTE_BLOCKS``, checked
against ``np.searchsorted`` on every row, and ``route_rows`` whole with
the parent's search and with the blocked count (no kernel: XLA alone).
"""

import functools
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _rand(key, shape, dtype, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _grads(f, n):
    """args -> (out, *grads wrt the first n args) under a fixed cotangent
    (a cosine ramp), so kernel and reference see the same backward
    problem."""
    def run(*args):
        out, vjp = jax.vjp(lambda *a: f(*a, *args[n:]), *args[:n])
        cot = jax.tree.map(
            lambda o: jnp.cos(jnp.arange(o.size, dtype=jnp.float32)
                              ).reshape(o.shape).astype(o.dtype), out)
        return (out, *vjp(cot))
    return run


def case_flash(b, h, s, d, causal):
    """Both sides on the projections' (B, S, H*hd) layout, as
    ops/attention.py calls them: the kernel reads it as it is, the XLA
    path transposes to heads and back."""
    from flexflow_tpu.ops.pallas.flash_attention import \
        flash_attention_packed
    from flexflow_tpu.parallel.ring_attention import blockwise_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    args = [_rand(k, (b, s, h * d), jnp.bfloat16) for k in ks]

    def heads(x):
        return x.reshape(b, s, h, d).transpose(0, 2, 1, 3)

    def xla(q, k, v):
        out = blockwise_attention(heads(q), heads(k), heads(v), causal,
                                  block_size=512)
        return out.transpose(0, 2, 1, 3).reshape(b, s, h * d
                                                 ).astype(q.dtype)

    kern = _grads(lambda q, k, v: flash_attention_packed(
        q, k, v, h, causal, interpret=False), 3)
    return kern, _grads(xla, 3), args


def case_flash_window(b, h, s, d, window, block=None, rows=512):
    """The flash kernels against XLA's masked attention at shapes whose
    score matrix does not fit: the queries ``rows`` at a time
    (``lax.map``), each block against the ``rows + window`` keys it can
    see (all of them without a window), the mask by position."""
    from flexflow_tpu.ops.pallas.flash_attention import \
        flash_attention_packed

    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    args = [_rand(k, (b, s, h * d), jnp.bfloat16) for k in ks]
    n = s // rows
    back = s if window is None else window      # keys before a block's

    def keys_of(x, first, span):
        """The ``span`` key positions from ``first`` on (zeros before
        position 0) under a window, every key without one."""
        x = x.reshape(b, s, h, d)
        if not window:
            return x
        return jax.lax.dynamic_slice_in_dim(
            jnp.pad(x, ((0, 0), (back, 0), (0, 0), (0, 0))), first + back,
            span, axis=1)

    def xla(q, k, v):
        def one(i, qb):         # qb (b, rows, h, d)
            first = i * rows - back if window else 0    # of the keys
            span = rows + back if window else s
            kb, vb = keys_of(k, first, span), keys_of(v, first, span)
            scores = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                                preferred_element_type=jnp.float32
                                ) / d ** 0.5
            qpos = i * rows + jnp.arange(rows)[:, None]
            kpos = first + jnp.arange(span)[None, :]
            seen = (kpos >= 0) & (kpos <= qpos) & (qpos - kpos < back)
            prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("bhqk,bkhd->bqhd", prob.astype(vb.dtype), vb,
                              preferred_element_type=jnp.float32)

        blocks = q.reshape(b, n, rows, h, d).transpose(1, 0, 2, 3, 4)
        out = jax.lax.map(jax.checkpoint(lambda c: one(*c)),
                          (jnp.arange(n), blocks))
        return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h * d
                                                    ).astype(q.dtype)

    def forward(q, k, v):
        return flash_attention_packed(q, k, v, h, True, block, block,
                                      interpret=False, window=window)

    kern = _grads(forward, 3)
    kern.forward = forward
    return kern, _grads(xla, 3), args


def case_fused_ce(n, d, v, partial, chunk=None):
    """``chunk``: the XLA reference takes the tokens that many at a time
    (``lax.map``; its logits never stand whole), where all of them at
    once would not fit beside the gradients."""
    from flexflow_tpu.ops.pallas.fused_ce import (fused_linear_ce,
                                                  fused_linear_ce_partial)

    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = _rand(ks[0], (n, d), jnp.bfloat16)
    w = _rand(ks[1], (d, v), jnp.float32, 0.02)
    b = _rand(ks[2], (v,), jnp.float32, 0.02)
    # the partial (vocab-shard) form sees labels outside its slice too
    labels = jax.random.randint(ks[3], (n,), -v if partial else 0, 2 * v
                                if partial else v, jnp.int32)

    def ref_stats(x, w, b, labels):
        logits = jnp.dot(x, w.astype(x.dtype),
                         preferred_element_type=jnp.float32) + b
        lse = jax.nn.logsumexp(logits, axis=-1)
        inside = (labels >= 0) & (labels < v)
        corr = jnp.take_along_axis(
            logits, jnp.clip(labels, 0, v - 1)[:, None], axis=-1)[:, 0]
        return lse - jnp.where(inside, corr, 0.0), lse

    def ref_chunked(x, w, b, labels):
        # recomputed in the backward: a chunk's logits are not kept
        nll, lse = jax.lax.map(
            jax.checkpoint(lambda c: ref_stats(c[0], w, b, c[1])),
            (x.reshape(-1, chunk, d), labels.reshape(-1, chunk)))
        return nll.reshape(n), lse.reshape(n)

    stats = ref_chunked if chunk else ref_stats
    form = fused_linear_ce_partial if partial else fused_linear_ce

    def forward(x, w, b, labels):
        return form(x, w, b, labels, interpret=False)

    kern = _grads(forward, 3)
    kern.forward = forward      # run_case times it alone as well
    ref = _grads(stats if partial
                 else lambda x, w, b, l: stats(x, w, b, l)[0], 3)
    return kern, ref, [x, w, b, labels]


def case_scan(b, s, h, p, n, chunk):
    """Both sides on the mixer's own arrays: ``xBC`` as the projection
    leaves it, float32 time steps within 0.001-0.1 (``SSMIn``'s), ``A``
    from -1 to -h.  Returns y and the gradients of x, B, C, delta, A_log
    and D under one cotangent."""
    from flexflow_tpu.ops.pallas.ssd_scan import ssd_scan
    from flexflow_tpu.ops.ssm import ssd_chunked

    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    di = h * p
    xbc = jax.nn.silu(_rand(ks[0], (b, s, di + 2 * n), jnp.float32)
                      ).astype(jnp.bfloat16)
    dt = jnp.exp(jax.random.uniform(ks[1], (b, s, h), jnp.float32,
                                    np.log(0.001), np.log(0.1)))
    a_log = jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32))
    d = 1.0 + _rand(ks[2], (h,), jnp.float32, 0.1)

    def kernel(xbc, dt, a_log, d):
        return ssd_scan(xbc, dt, -jnp.exp(a_log), d, heads=h, head_dim=p,
                        state=n, chunk=chunk, interpret=False)

    def xla(xbc, dt, a_log, d):
        return ssd_chunked(xbc[..., :di].reshape(b, s, h, p), dt,
                           -jnp.exp(a_log), xbc[..., di:di + n],
                           xbc[..., di + n:], d, chunk).reshape(b, s, di)

    def leaves(forward):
        def run(*args):
            y, d_xbc, *rest = _grads(forward, 4)(*args)
            return (y, d_xbc[..., :di], d_xbc[..., di:di + n],
                    d_xbc[..., di + n:], *rest)
        run.forward = forward   # run_case times it alone as well
        return run

    return leaves(kernel), leaves(xla), [xbc, dt, a_log, d]


CASES = [
    ("flash b16 h12 s512 d64 causal", case_flash, (16, 12, 512, 64, True)),
    ("flash b16 h12 s512 d64 full", case_flash, (16, 12, 512, 64, False)),
    ("flash b16 h12 s1024 d64 causal", case_flash, (16, 12, 1024, 64, True)),
    ("flash b1 h4 s8192 d64 causal", case_flash, (1, 4, 8192, 64, True)),
    ("fused_ce n8192 d768 v32768", case_fused_ce, (8192, 768, 32768, False)),
    ("fused_ce partial n8192 d768 v8192 (vocab TP /4)", case_fused_ce,
     (8192, 768, 8192, True)),
]
# the token cells' own heads (gpt2_small, granite_4_0_h_micro,
# moonlight_16b_a3b) and a quarter of Granite's as a vocabulary shard
CE_CASES = [
    ("fused_ce n16384 d768 v50257", case_fused_ce,
     (16384, 768, 50257, False, 2048)),
    ("fused_ce n16384 d2048 v100352", case_fused_ce,
     (16384, 2048, 100352, False, 2048)),
    ("fused_ce n16384 d2048 v20480", case_fused_ce,
     (16384, 2048, 20480, False, 2048)),
    ("fused_ce partial n16384 d2048 v25088 (vocab TP /4)", case_fused_ce,
     (16384, 2048, 25088, True, 2048)),
]


# granite_4_0_h_micro's scan, a layer of the cell
SCAN_CASES = [
    ("ssd_scan b2 s8192 h64 p64 n128 chunk256", case_scan,
     (2, 8192, 64, 64, 128, 256)),
]


# the Laguna cell's attention: a sliding layer (72 heads, window 512) and a
# full layer (48 heads), and the (block, pieces) timed beside the rule's own
WIN_SHAPE = (2, 72, 8192, 128, 512)
WIN_TILES = ((512, 128, 128), (512, 256, 256), (512, 512, 512),
             (256, 256, 256))


def run_window():
    """The rule's windowed call checked against XLA and timed, the
    candidates timed, and the full layer's causal call beside them."""
    import importlib

    fa = importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")
    b, h, s, d, window = WIN_SHAPE
    failed = 0
    cases = [(f"flash window b{b} h{h} s{s} d{d} w{window}",
              case_flash_window, WIN_SHAPE),
             (f"flash b{b} h48 s{s} d{d} causal", case_flash_window,
              (b, 48, s, d, None))]
    for name, make, shape in cases:
        try:
            rec = run_case(name, make, shape)
            rec["ok"] = True
        except Exception as e:
            failed += 1
            rec = {"case": name, "ok": False, "error": type(e).__name__,
                   "message": str(e)[-3000:]}
            traceback.print_exc(limit=3)
        print(json.dumps(rec), flush=True)
    pieces = fa._WINDOW_FWD_PIECE, fa._WINDOW_BWD_PIECE
    rec = {"case": "flash window candidates (block/forward/backward "
                   "pieces)", "forward_ms": {}, "forward_backward_ms": {}}
    try:
        for block, fwd, bwd in WIN_TILES:
            fa._WINDOW_FWD_PIECE, fa._WINDOW_BWD_PIECE = fwd, bwd
            fa._make_flash.cache_clear()
            kern, _, args = case_flash_window(*WIN_SHAPE, block=block)
            key = f"{block}/{fwd}/{bwd}"
            rec["forward_ms"][key] = _pipelined(jax.jit(kern.forward),
                                                args, 5)
            rec["forward_backward_ms"][key] = _pipelined(jax.jit(kern),
                                                         args, 5)
        rec["ok"] = True
    except Exception as e:
        failed += 1
        rec.update(ok=False, error=type(e).__name__, message=str(e)[-3000:])
        traceback.print_exc(limit=3)
    finally:
        fa._WINDOW_FWD_PIECE, fa._WINDOW_BWD_PIECE = pieces
        fa._make_flash.cache_clear()
    print(json.dumps(rec), flush=True)
    return failed


# grouped products: (rows, d, d_ff, groups) of the Moonlight cell, and the
# row / depth / column tiles timed beside the rule's own (None); a depth or
# column entry of 0 is the whole dimension, and a fourth entry the pieces
# a row tile that a group boundary cuts is walked in (the row forms';
# as many rows as the tile: the whole tile a visit)
GMM_SHAPE = (24576, 2048, 1408, 8)
GMM_TILES = (None, (256, 0, 0), (512, 0, 0), (1024, 0, 0), (512, 0, 0, 512),
             (512, 0, 768), (512, 1024, 0))


def _gmm_inputs():
    m, d, f, g = GMM_SHAPE
    rng = np.random.RandomState(31)
    # loads of 0.85-1.17 of the balanced 1536 rows, as the cell's routers
    # give (PERF.md section 6, PR 28)
    sizes = np.round(m / 2 / g * rng.uniform(0.85, 1.17, g)).astype(np.int32)
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    bf = jnp.bfloat16
    return dict(
        sizes=jnp.asarray(sizes), rows=_rand(ks[0], (m, d), bf),
        h=_rand(ks[1], (m, f), bf), d_y=_rand(ks[2], (m, d), bf),
        d_gate=_rand(ks[3], (m, f), bf),
        w_up=_rand(ks[4], (g, d, f), bf, 0.03),
        w_down=_rand(ks[5], (g, f, d), bf, 0.03))


def _pipelined(fn, args, calls=20):
    """ms a call with ``calls`` of them in flight: the device's time, not
    the host's dispatch, where a call takes a millisecond."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(calls)]
        jax.block_until_ready(outs)
        best = min(best, (time.perf_counter() - t0) / calls)
    return round(best * 1e3, 4)


def run_gmm():
    """One record a product of the layer: ragged_dot's ms, the kernel's
    at each candidate, their agreement; then the whole feed-forward."""
    from flexflow_tpu.ops import expert_share
    from flexflow_tpu.ops.pallas import grouped_mm as gm

    x = _gmm_inputs()
    sizes, f32, pieces = x["sizes"], jnp.float32, gm._SUB_ROWS
    m, d, f, g = GMM_SHAPE

    def ragged(a, w):
        return jax.lax.ragged_dot(a, w, sizes, preferred_element_type=f32)

    def pulled(which, a, w, ct):
        """What autodiff of ``ragged(a, w)`` runs for one operand's
        gradient under a float32 cotangent."""
        if which == "a":
            return jax.vjp(lambda a_: ragged(a_, w), a)[1](
                ct.astype(f32))[0]
        return jax.vjp(lambda w_: ragged(a, w_), w)[1](ct.astype(f32))[0]

    products = [
        # name, form, kernel args, out type, reference
        ("up: rows x w_up -> f32", gm.ff_gmm, (x["rows"], x["w_up"]), f32,
         lambda: ragged(x["rows"], x["w_up"])),
        ("down: h x w_down -> bf16", gm.ff_gmm, (x["h"], x["w_down"]),
         None, lambda: ragged(x["h"], x["w_down"]).astype(jnp.bfloat16)),
        ("d_h: d_y x w_down^T", gm.ff_gmm_t, (x["d_y"], x["w_down"]), None,
         lambda: pulled("a", x["h"], x["w_down"], x["d_y"])),
        ("d_rows: d_gate x w_up^T", gm.ff_gmm_t, (x["d_gate"], x["w_up"]),
         None, lambda: pulled("a", x["rows"], x["w_up"], x["d_gate"])),
        ("dw_up: rows^T x d_gate", gm.ff_gmm_dw, (x["rows"], x["d_gate"]),
         None, lambda: pulled("w", x["rows"], x["w_up"], x["d_gate"])),
        ("dw_down: h^T x d_y", gm.ff_gmm_dw, (x["h"], x["d_y"]), None,
         lambda: pulled("w", x["h"], x["w_down"], x["d_y"])),
    ]
    failed = 0
    live = int(sizes.sum())
    for name, form, args, out_dtype, ref in products:
        rec = {"case": f"gmm {name}", "group_sizes": sizes.tolist()}
        rows_out = form is not gm.ff_gmm_dw
        try:
            ref_fn = jax.jit(ref)
            want = np.asarray(jax.block_until_ready(ref_fn()), np.float32)
            rec["ragged_dot_ms"] = _pipelined(ref_fn, ())
            if rows_out:
                # what ragged_dot leaves in the rows of no group
                rec["ragged_dot_tail_is_zero"] = not want[live:].any()
                want = want[:live]
            rec["kernel_ms"], rec["rel_err"] = {}, {}
            k, n = args[0].shape[1], want.shape[-1]
            for cand in GMM_TILES:
                tiles = cand and (cand[0], cand[1] or k, cand[2] or n)
                if tiles and rows_out and k % tiles[1]:
                    continue        # a depth tile divides the depth
                gm._SUB_ROWS = cand[3] if cand and len(cand) > 3 else pieces
                fn = jax.jit(lambda a, b, t=tiles: form(
                    a, b, sizes, out_dtype, t))
                got = np.asarray(jax.block_until_ready(fn(*args)),
                                 np.float32)
                key = "rule" if cand is None else "x".join(map(str, tiles)) \
                    + (f"/{cand[3]}" if len(cand) > 3 else "")
                rec["kernel_ms"][key] = _pipelined(fn, args)
                if rows_out:
                    if got[live:].any():
                        raise RuntimeError(f"{key}: tail rows not 0")
                    got = got[:live]
                rec["rel_err"][key] = round(_rel_err(got, want), 5)
                if rec["rel_err"][key] > 3e-2:
                    raise RuntimeError(f"{key}: disagrees with ragged_dot")
            rec["ok"] = True
        except Exception as e:
            failed += 1
            rec.update(ok=False, error=type(e).__name__,
                       message=str(e)[-3000:])
            traceback.print_exc(limit=3)
        finally:
            gm._SUB_ROWS = pieces
        print(json.dumps(rec), flush=True)

    # the whole feed-forward with its backward, as HeldExperts calls it
    ws = [_rand(k, sh, f32, 0.03) for k, sh in zip(
        jax.random.split(jax.random.PRNGKey(3), 3),
        ((g, d, f), (g, d, f), (g, f, d)))]

    # the cotangent of a row of no group is 0, as combine's backward
    # makes it; the rows' gradient is compared over the groups' rows
    d_y = jnp.where(jnp.arange(m)[:, None] < live, x["d_y"], 0)

    def step(rows, *ws):
        loss, (d_rows, *d_ws) = jax.value_and_grad(
            lambda rows, *ws: (expert_share.grouped_gated_ffn(
                rows, sizes, *ws).astype(f32) * d_y).sum(),
            (0, 1, 2, 3))(rows, *ws)
        return (loss, d_rows[:live], *d_ws)

    rec = {"case": "gmm gated feed-forward, forward + backward"}
    try:
        gate = expert_share.pallas.flash_enabled
        got_fn = jax.jit(step)
        got = jax.block_until_ready(got_fn(x["rows"], *ws))
        rec["kernel_ms"] = _pipelined(got_fn, (x["rows"], *ws), 5)
        expert_share.pallas.flash_enabled = lambda: False
        try:
            want_fn = jax.jit(lambda *a: step(*a))
            want = jax.block_until_ready(want_fn(x["rows"], *ws))
            rec["ragged_dot_ms"] = _pipelined(want_fn, (x["rows"], *ws), 5)
        finally:
            expert_share.pallas.flash_enabled = gate
        rec["rel_err"] = [round(_rel_err(a, b), 5) for a, b in zip(
            jax.tree.leaves(got), jax.tree.leaves(want))]
        rec["ok"] = max(rec["rel_err"]) <= 3e-2
        failed += not rec["ok"]
    except Exception as e:
        failed += 1
        rec.update(ok=False, error=type(e).__name__, message=str(e)[-3000:])
        traceback.print_exc(limit=3)
    print(json.dumps(rec), flush=True)
    return failed


# route_rows at the three expert cells' shapes: (cell, tokens, experts held,
# rows_capacity, top_k, router outputs); each pair is picked at the
# balanced load, top_k / router outputs, which fills half the buffer.  The
# search of the parent's route_rows in each of jnp.searchsorted's methods
# and the blocked count at each of ROUTE_BLOCKS, all against np.searchsorted
ROUTE_SHAPES = (("moonlight_16b_a3b", 16384, 8, 24576, 6, 64),
                ("lfm2_8b_a1b", 16384, 8, 32768, 4, 32),
                ("laguna_s_2_1", 16384, 8, 10240, 10, 256))
ROUTE_BLOCKS = (128, 256, 512, 1024, 2048)
ROUTE_METHODS = ("scan", "scan_unrolled", "sort", "compare_all")


def run_route():
    """One record a cell: ms a call of each search of the rows' pairs
    (pipelined), whether it equals ``np.searchsorted`` on every row, and
    the whole ``route_rows`` with the parent's search and with the
    blocked count at the rule's block."""
    from flexflow_tpu.ops import expert_share as es

    failed = 0
    for cell, t, e, rows, top_k, n_router in ROUTE_SHAPES:
        rng = np.random.RandomState(39)
        gates = (rng.rand(t, e) < top_k / n_router).astype(np.float32)
        upto = np.cumsum(gates.T.reshape(-1) > 0, dtype=np.int32)
        want = np.searchsorted(upto, np.arange(1, rows + 1), side="left")
        rec = {"case": f"route {cell}", "pairs": t * e, "rows": rows,
               "picked": int(upto[-1]), "rule_block": es.route_block(t * e),
               "search_ms": {}, "exact": {}}
        searches = {f"searchsorted.{m}": functools.partial(
            jnp.searchsorted, side="left", method=m) for m in ROUTE_METHODS}
        searches.update({f"blocked.{b}": functools.partial(
            lambda u, q, b: es.first_reaching(u, q.shape[0], b), b=b)
            for b in ROUTE_BLOCKS})
        args = (jnp.asarray(upto), jnp.arange(1, rows + 1, dtype=jnp.int32))
        for key, search in searches.items():
            try:
                fn = jax.jit(search)
                got = np.asarray(jax.block_until_ready(fn(*args)))
                rec["exact"][key] = bool((got == want).all())
                rec["search_ms"][key] = _pipelined(fn, args)
                failed += not rec["exact"][key]
            except Exception as e_:
                failed += 1
                rec["search_ms"][key] = f"{type(e_).__name__}: {e_}"[:300]
                traceback.print_exc(limit=3)
        rec["route_rows_ms"] = {}
        blocked = es.first_reaching
        try:
            for side, search in (
                    ("parent", lambda u, r, b: jnp.searchsorted(
                        u, jnp.arange(1, r + 1, dtype=jnp.int32))),
                    ("blocked", blocked)):
                es.first_reaching = search
                fn = jax.jit(lambda g: es.route_rows(g, rows))
                rec["route_rows_ms"][side] = _pipelined(
                    fn, (jnp.asarray(gates),))
        finally:
            es.first_reaching = blocked
        rec["ok"] = all(rec["exact"].values()) and len(rec["exact"]) == len(
            searches)
        print(json.dumps(rec), flush=True)
    return failed


# laguna_s_2_1's rotary positions: a sliding layer's queries (72 heads, all
# 128 dimensions turned, theta 10 000), a full layer's (48 heads, 64 of 128
# turned, YaRN) and the keys of both (8 heads); and the (rows a grid step,
# rows a product with the pairing matrix) timed beside the kernel's own
ROPE_YARN = {"dim": 64, "rope_theta": 500000.0, "rope_type": "yarn",
             "factor": 32.0, "original_max_position_embeddings": 4096,
             "beta_fast": 32.0, "beta_slow": 1.0}
ROPE_SHAPES = ((72, {"dim": 128, "rope_theta": 10000.0}), (48, ROPE_YARN),
               (8, {"dim": 128, "rope_theta": 10000.0}), (8, ROPE_YARN))
ROPE_BLOCKS = ((512, 128), (512, 512), (256, 256), (1024, 256))
ROPE_SHAPE = (2, 8192, 3072, 128)   # batch, positions, hidden, a head


def run_rope():
    """``ff_rope`` against ``apply_rope`` on the 4-D view of the same
    array: the turned values, the gradient of the input, how far each
    side's gradient lies from the float32 computation of it, and each
    side's forward and forward + backward ms, alone and behind the q
    projection of a sliding layer."""
    from flexflow_tpu.ops.pallas import rope
    from flexflow_tpu.ops.seq_gated import apply_rope, rotary_table

    b, s, d, hd = ROPE_SHAPE
    failed = 0

    def sides(heads, rule):
        cos, sin = rotary_table(rule, s)
        return (lambda q: rope.rope_packed(q, cos, sin, heads),
                lambda q: apply_rope(q.reshape(b, s, heads, hd), cos, sin
                                     ).reshape(b, s, heads * hd))

    def times(rec, side, fn, args, n):
        rec[side + "_forward_ms"] = _pipelined(jax.jit(fn), args, 10)
        rec[side + "_forward_backward_ms"] = _pipelined(
            jax.jit(_grads(fn, n)), args, 10)

    for heads, rule in ROPE_SHAPES:
        rec = {"case": f"rope b{b} s{s} h{heads} d{hd} r{rule['dim']} "
                       f"{rule.get('rope_type', 'default')}"}
        try:
            kernel, xla = sides(heads, rule)
            q = _rand(jax.random.PRNGKey(heads), (b, s, heads * hd),
                      jnp.bfloat16)
            got = jax.jit(_grads(kernel, 1))(q)
            want = jax.jit(_grads(xla, 1))(q)
            exact = jax.jit(_grads(xla, 1))(q.astype(jnp.float32))
            rec["rel_err"] = [round(_rel_err(g, w), 6)
                              for g, w in zip(got, want)]
            rec["differing"] = [int(jnp.sum(g != w))
                                for g, w in zip(got, want)]
            rec["gradient_from_float32"] = {
                "kernel": round(_rel_err(got[1], exact[1]), 6),
                "xla": round(_rel_err(want[1], exact[1]), 6)}
            times(rec, "kernel", kernel, [q], 1)
            times(rec, "xla", xla, [q], 1)
            rec["ok"] = max(rec["rel_err"]) <= 1e-2
            failed += not rec["ok"]
        except Exception as e:
            failed += 1
            rec.update(ok=False, error=type(e).__name__,
                       message=str(e)[-3000:])
            traceback.print_exc(limit=3)
        print(json.dumps(rec), flush=True)

    heads, rule = ROPE_SHAPES[0]
    kernel, xla = sides(heads, rule)
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = _rand(ks[0], (b, s, d), jnp.bfloat16)
    w = _rand(ks[1], (d, heads * hd), jnp.float32, d ** -0.5)

    def proj(x, w):
        return jnp.einsum("bsd,de->bse", x, w.astype(x.dtype),
                          preferred_element_type=jnp.float32
                          ).astype(x.dtype)

    rec = {"case": f"q projection + rope b{b} s{s} h{heads} d{hd}"}
    try:
        behind = {"kernel": lambda x, w: kernel(proj(x, w)),
                  "xla": lambda x, w: xla(proj(x, w)), "product": proj}
        got, want = (jax.jit(_grads(behind[k], 2))(x, w)
                     for k in ("kernel", "xla"))
        rec["rel_err"] = [round(_rel_err(g, w_), 6)
                          for g, w_ in zip(got, want)]
        for side, fn in behind.items():
            times(rec, side, fn, [x, w], 2)
        rec["ok"] = max(rec["rel_err"]) <= 1e-2
        failed += not rec["ok"]
    except Exception as e:
        failed += 1
        rec.update(ok=False, error=type(e).__name__, message=str(e)[-3000:])
        traceback.print_exc(limit=3)
    print(json.dumps(rec), flush=True)

    blocks = rope._ROWS, rope._SUB_ROWS
    rec = {"case": "rope candidates (rows a step/rows a product)",
           "forward_ms": {}}
    try:
        q = _rand(jax.random.PRNGKey(1), (b, s, heads * hd), jnp.bfloat16)
        for rows, sub in (blocks, *ROPE_BLOCKS):
            rope._ROWS, rope._SUB_ROWS = rows, sub
            rope._make_rope.cache_clear()
            rec["forward_ms"][f"{rows}/{sub}"] = _pipelined(
                jax.jit(sides(heads, rule)[0]), [q], 10)
        rec["ok"] = True
    except Exception as e:
        failed += 1
        rec.update(ok=False, error=type(e).__name__, message=str(e)[-3000:])
        traceback.print_exc(limit=3)
    finally:
        rope._ROWS, rope._SUB_ROWS = blocks
        rope._make_rope.cache_clear()
    print(json.dumps(rec), flush=True)
    return failed


def _rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _timed(fn, args, repeats=5):
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    return sorted(samples)[len(samples) // 2]


def run_case(name, make, shape, tol=3e-2):
    from flexflow_tpu.utils.profiling import pallas_kernel_calls

    kern, ref, args = make(*shape)
    forward = getattr(kern, "forward", None)
    rec = {"case": name}
    t0 = time.perf_counter()
    compiled = jax.jit(kern).lower(*args).compile()
    rec["compile_s"] = round(time.perf_counter() - t0, 2)
    rec["kernels"] = pallas_kernel_calls(compiled.as_text())
    if not rec["kernels"]:
        raise RuntimeError("compiled program holds no TPU custom call")
    got = jax.block_until_ready(compiled(*args))
    want = jax.block_until_ready(jax.jit(ref)(*args))
    rec["rel_err"] = [round(_rel_err(g, w), 5)
                      for g, w in zip(jax.tree.leaves(got),
                                      jax.tree.leaves(want))]
    rec["kernel_ms"] = round(_timed(compiled, args) * 1e3, 3)
    rec["xla_ref_ms"] = round(_timed(jax.jit(ref), args) * 1e3, 3)
    if forward is not None:
        # the forward alone, and by difference the backward; a few calls
        # in flight, so the device's time and not the host's dispatch
        rec["forward_ms"] = _pipelined(jax.jit(forward), args, 5)
        rec["forward_backward_ms"] = _pipelined(compiled, args, 5)
        rec["backward_ms"] = round(
            rec["forward_backward_ms"] - rec["forward_ms"], 4)
    if getattr(ref, "forward", None) is not None:
        rec["xla_forward_ms"] = _pipelined(jax.jit(ref.forward), args, 5)
        rec["xla_forward_backward_ms"] = _pipelined(jax.jit(ref), args, 5)
    if max(rec["rel_err"]) > tol:
        raise RuntimeError(f"kernel disagrees with its XLA reference: "
                           f"relative errors {rec['rel_err']} > {tol}")
    return rec


def main(argv):
    if argv not in ([], ["gmm"], ["ce"], ["win"], ["scan"], ["rope"],
                    ["route"]):
        raise SystemExit(f"chip_kernels: takes no argument, 'gmm' (the "
                         f"grouped products alone), 'ce' (the fused "
                         f"head alone), 'win' (the windowed flash alone), "
                         f"'scan' (the state-space scan alone), 'rope' "
                         f"(the rotary positions alone) or 'route' (the "
                         f"held experts' row search alone), got {argv}")
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"chip_kernels: backend {jax.default_backend()!r} is not a "
            f"TPU; Mosaic compiles only there")
    failed = 0
    cases = {"gmm": [], "ce": CE_CASES, "win": [], "scan": SCAN_CASES,
             "rope": [], "route": []}.get(
        "".join(argv), CASES + CE_CASES + SCAN_CASES)
    for name, make, shape in cases:
        try:
            rec = run_case(name, make, shape)
            rec["ok"] = True
        except Exception as e:  # report the compiler's message, go on
            failed += 1
            rec = {"case": name, "ok": False, "error": type(e).__name__,
                   "message": str(e)[-3000:]}
            traceback.print_exc(limit=3)
        print(json.dumps(rec), flush=True)
    if argv in ([], ["win"]):
        failed += run_window()
    if argv in ([], ["gmm"]):
        failed += run_gmm()
    if argv in ([], ["rope"]):
        failed += run_rope()
    if argv in ([], ["route"]):
        failed += run_route()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
