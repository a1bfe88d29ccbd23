"""Run every Pallas kernel through Mosaic at the shapes the models use.

    python tools/chip_kernels.py               # on the chip

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
x 32k vocab and its vocab-TP partial form.
"""

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


def case_fused_ce(n, d, v, partial):
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

    if partial:
        kern = _grads(lambda x, w, b, l: fused_linear_ce_partial(
            x, w, b, l, interpret=False), 3)
        ref = _grads(ref_stats, 3)
    else:
        kern = _grads(lambda x, w, b, l: fused_linear_ce(
            x, w, b, l, interpret=False), 3)
        ref = _grads(lambda x, w, b, l: ref_stats(x, w, b, l)[0], 3)
    return kern, ref, [x, w, b, labels]


CASES = [
    ("flash b16 h12 s512 d64 causal", case_flash, (16, 12, 512, 64, True)),
    ("flash b16 h12 s512 d64 full", case_flash, (16, 12, 512, 64, False)),
    ("flash b16 h12 s1024 d64 causal", case_flash, (16, 12, 1024, 64, True)),
    ("flash b1 h4 s8192 d64 causal", case_flash, (1, 4, 8192, 64, True)),
    ("fused_ce n8192 d768 v32768", case_fused_ce, (8192, 768, 32768, False)),
    ("fused_ce partial n8192 d768 v8192 (vocab TP /4)", case_fused_ce,
     (8192, 768, 8192, True)),
]


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
    if max(rec["rel_err"]) > tol:
        raise RuntimeError(f"kernel disagrees with its XLA reference: "
                           f"relative errors {rec['rel_err']} > {tol}")
    return rec


def main(argv):
    if argv:
        raise SystemExit(f"chip_kernels: takes no arguments, got {argv}")
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"chip_kernels: backend {jax.default_backend()!r} is not a "
            f"TPU; Mosaic compiles only there")
    failed = 0
    for name, make, shape in CASES:
        try:
            rec = run_case(name, make, shape)
            rec["ok"] = True
        except Exception as e:  # report the compiler's message, go on
            failed += 1
            rec = {"case": name, "ok": False, "error": type(e).__name__,
                   "message": str(e)[-3000:]}
            traceback.print_exc(limit=3)
        print(json.dumps(rec), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
