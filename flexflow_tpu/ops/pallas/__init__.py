"""Pallas TPU kernels for the hot ops.

These are the hand-scheduled compute paths of the framework (the analog of
the reference's hand-written CUDA kernels, e.g. nmt/embed.cu's gather /
scatter-add and the cuDNN leaf tasks): XLA fuses most elementwise work into
the MXU matmuls on its own, so Pallas is reserved for the ops where manual
VMEM tiling beats the compiler.

What a kernel must show to be in this package: a benchmark cell it won on
the chip, end to end, named here.  ``flash_attention.py`` won
``gpt2_small.train_1chip_b16_s1024`` (+45.8%, PR 27) and carries
``moonlight_16b_a3b.train_1chip_b2_s8192``; ``fused_ce.py`` is what lets
the token cells' vocabulary-sized heads fit beside their activations
(Granite's logits would be 6.6 GB), and since PR 33, with one backward
kernel for dX, dW and db and tiles picked from the width, won
``gpt2_small.train_1chip_b16_s1024`` (+13.2%) and
``granite_4_0_h_micro.train_1chip_b2_s8192_ref2`` (+5.9%);
``grouped_mm.py`` (``ff_gmm``, ``ff_gmm_t``, ``ff_gmm_dw``: the grouped
products of the experts a chip holds) won the Moonlight cell, +11.3%
(PR 31: a layer's product 0.49-0.58 ms where XLA's own grouped matmul
under ``jax.lax.ragged_dot`` took 0.94-2.3, 85.8 -> 31.4 ms a step);
``ssd_scan.py`` (``ff_ssd_fwd``, ``ff_ssd_bwd``: the state-space scan
with a chunk's decay-weighted scores made in VMEM) won
``granite_4_0_h_micro.train_1chip_b2_s8192_ref2``, +9.35% (PR 35: the
nine scans 140.3 -> 50.7 ms a step, a layer's forward 3.6 -> 1.2 ms and
its backward 8.3 -> 3.2); ``rope.py`` (``ff_rope``, ``ff_rope_t``: rotary
positions in the layout the projections write and the flash kernels
read) won ``laguna_s_2_1.train_1chip_b2_s8192_ref2``, +10.8% (PR 37: a
sliding layer's rotary chain 21 -> 3.0 ms a step and a full layer's 12 ->
2.2, the step 841.3 -> 758.8 ms).  The
max-pool, avg-pool and batch-norm kernels that lost their cells left in
PR 30 (PERF.md section 6).

Which path an operator takes is decided from what the code observes and
never by a switch: the backend (:func:`flash_enabled`, the one gate every
caller shares) and the shapes and types, by the rules that live beside
each kernel (``flash_attention._layout``, ``_pick_block``;
``grouped_mm._pick_tiles``; ``FFModel._fusion_ok`` and
``fused_ce._pick_tiles``; ``ssd_scan.fits``; ``rope.fits``).  A new
kernel joins the same way.

Kernels run compiled (Mosaic) on TPU; interpreter mode is for the CPU test
suite, whose ``pallas_kernels`` fixture (tests/conftest.py) patches the
gate.
"""

from flexflow_tpu.ops.pallas.flash_attention import \
    KEPT_RESULTS as _FLASH_RESULTS, flash_attention

# Results the kernels name (``jax.ad_checkpoint.checkpoint_name``) because
# they are dearer to make again than to hold: a block that a model class
# recomputes in the backward pass keeps these and nothing else
# (FFModel._run_recomputed).  A kernel joins by naming its results in its
# own module and adding them here.  (The scan's ``y`` and entering states
# are not named: kept, they cost 2.15 GB for +0.87%, PERF.md section 6,
# PR 35.)
KEPT_RESULTS = (*_FLASH_RESULTS,)


def flash_enabled() -> bool:
    """The kernel gate: the backend is a TPU (compiled via Mosaic; on a
    v5e, forward + backward at the GPT-2 cell's shape, b16 h12 s1024 d64
    causal bf16, took 3.15 ms against 14.14 for XLA's blockwise
    attention, and 3.03 against 15.33 at b1 h4 s8192 —
    tools/chip_kernels.py, host clock; PERF.md section 6, PR 27).
    Elsewhere the kernels would run in interpret mode, which is for
    tests and too slow for training."""
    import jax

    return jax.default_backend() == "tpu"


def traced_once(fn, *avals):
    """``fn`` traced now, for these shapes and types, to a function of
    flat arrays that binds the traced equations wherever it is called: a
    model's layers, the blocks it recomputes and their derivatives then
    share one trace and one lowering of every kernel body, and each call
    site's equations still carry its own operator's name.  (An inline
    ``jit`` shares a trace only among callers in one tracing context, and
    a step has four: 16 traces of the grouped products' kernels a
    Moonlight step where 6 do, and ``setup_s`` outside its bound; PERF.md
    section 6, PR 31.  ``grouped_mm.py``, ``ssd_scan.py`` and ``rope.py``
    build their custom-VJP passes through it.)"""
    import jax
    import jax.extend

    closed = jax.make_jaxpr(fn)(*avals)
    return jax.extend.core.jaxpr_as_fun(closed), closed.out_avals


__all__ = ["KEPT_RESULTS", "flash_attention", "flash_enabled"]
