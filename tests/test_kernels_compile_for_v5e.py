"""The fused head's kernels, the windowed flash kernels and the
state-space scan's kernels compiled by Mosaic for a described TPU v5e, at
the widths the benchmark's cells run, at the widest the fusion takes and
at the corners of the scan's rule: interpret mode says nothing about
what the chip's compiler accepts (VMEM above all), and a compile here
costs no chip time.  Nothing runs: a pass is not a measurement."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("d,v,dtype", [
    (768, 50257, "bfloat16"),      # gpt2_small
    (2048, 100352, "bfloat16"),    # granite_4_0_h_micro (moonlight: 20480)
    (4096, 32000, "bfloat16"),     # the widest head _fusion_ok lets in
    (2048, 32000, "float32"),
])
def test_fused_head_compiles_at_the_tiles_the_shapes_pick(one_chip, d, v,
                                                          dtype):
    from flexflow_tpu.ops.pallas import fused_ce as ce
    n = 16384

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    def step(x, w, b, labels):
        loss = lambda x, w, b: ce.fused_linear_ce(
            x, w, b, labels, interpret=False).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)

    text = jax.jit(step).lower(
        shape((n, d), dtype), shape((d, v), jnp.float32),
        shape((v,), jnp.float32), shape((n,), jnp.int32)
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2, \
        "forward and one backward kernel"
    logits = [m for m in re.findall(r"\w+\[(\d+),(\d+)\]", text)
              if int(m[0]) >= n and int(m[1]) >= v]
    assert not logits, "an array of tokens x vocabulary reached HBM"


@pytest.mark.parametrize("heads,window,names", [
    # laguna_s_2_1's sliding layers: blocks of 512, the band of two
    (72, 512, ("ff_flash_win_fwd", "ff_flash_win_bwd_dkv",
               "ff_flash_win_bwd_dq")),
    # a window of several blocks (1024 x 1024 tiles, a band of five)
    (8, 4096, ("ff_flash_win_fwd", "ff_flash_win_bwd_dkv",
               "ff_flash_win_bwd_dq")),
    # and its full layers: the causal kernels under their own names
    (48, None, ("ff_flash_fwd", "ff_flash_bwd_dkv", "ff_flash_bwd_dq")),
])
def test_flash_compiles_at_the_laguna_cells_shapes(one_chip, heads, window,
                                                   names):
    import importlib

    fa = importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")
    x = jax.ShapeDtypeStruct((2, 8192, heads * 128), jnp.bfloat16,
                             sharding=one_chip)

    def step(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: fa.flash_attention_packed(
                q, k, v, heads, True, interpret=False, window=window
            ).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    text = jax.jit(step).lower(x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in names:
        assert re.search(rf"\b{name}\b", text), name
    assert ("ff_flash_win_" in text) == (window is not None)


@pytest.mark.parametrize("heads,head_dim,state,chunk,dtype", [
    (64, 64, 128, 256, "bfloat16"),    # granite_4_0_h_micro
    # the corners of ``ssd_scan.fits`` (compiled, not timed)
    (64, 64, 128, 256, "float32"),     # the most VMEM the rule takes
    (8, 32, 128, 128, "float32"),      # the shorter chunk, one group
    (16, 16, 128, 128, "bfloat16"),    # the narrowest head, two groups
    (256, 16, 128, 256, "bfloat16"),   # the most groups of heads
])
def test_scan_compiles_and_no_chunk_matrix_reaches_hbm(one_chip, heads,
                                                       head_dim, state,
                                                       chunk, dtype):
    from flexflow_tpu.ops.pallas import ssd_scan as ss

    b, s = 2, 8 * chunk
    assert ss.fits(chunk, heads, head_dim, state, dtype)

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    def step(xbc, dt, a, d):
        return jax.value_and_grad(
            lambda *args: ss.ssd_scan(
                *args, heads=heads, head_dim=head_dim, state=state,
                chunk=chunk, interpret=False).astype(jnp.float32).sum(),
            (0, 1, 2, 3))(xbc, dt, a, d)

    text = jax.jit(step).lower(
        shape((b, s, heads * head_dim + 2 * state), dtype),
        shape((b, s, heads), jnp.float32), shape((heads,), jnp.float32),
        shape((heads,), jnp.float32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("ff_ssd_fwd", "ff_ssd_bwd"):
        assert re.search(rf"\b{name}\b", text), name
    # a decay matrix or m would be (.., heads, chunk, chunk)
    assert not re.search(rf"\[[\d,]*{chunk},{chunk}\]", text)
