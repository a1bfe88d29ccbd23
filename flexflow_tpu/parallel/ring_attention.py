"""Ring attention: context parallelism over the sequence axis.

Each device holds a sequence shard of Q, K, V.  K/V blocks rotate around the
ring via ``ppermute`` while every device accumulates its queries' attention
over the passing blocks with numerically-stable streaming softmax
(flash-attention-style running max / denominator).  Communication rides
neighbor links (ICI-friendly); memory per chip is O(S/P).  Backward is jax
autodiff through the scan + ppermute (the transpose of a ring is the
reverse ring).

This is new capability relative to the reference (no attention ops exist
there); it fills the CP/ring-attention row of SURVEY.md §2.6 and is the
long-context path required of the framework.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _stream_block(q, k, v, m, l, acc, mask):
    """One streaming-softmax accumulation step.

    q: (B, H, Sq, d), k/v: (B, H, Sk, d); m/l: (B, H, Sq); acc like q.
    mask: (Sq, Sk) additive (-inf where disallowed) or None.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = s + mask
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> nan
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    if mask is not None:
        p = jnp.where(jnp.isinf(s), 0.0, p)
    corr = jnp.exp(jnp.where(jnp.isfinite(m), m - safe_m, -jnp.inf))
    corr = jnp.where(jnp.isfinite(m), corr, 0.0)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(p.dtype),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _causal_mask(sq: int, sk: int, q_off, k_off):
    qpos = q_off + jnp.arange(sq)[:, None]
    kpos = k_off + jnp.arange(sk)[None, :]
    return jnp.where(qpos >= kpos, 0.0, -jnp.inf)


def blockwise_attention(q, k, v, causal: bool = False,
                        block_size: Optional[int] = None,
                        q_offset: int = 0, k_offset: int = 0):
    """Single-device streaming attention over K/V blocks (O(S_block^2)
    memory).  q,k,v: (B, H, S, d) -> (B, H, Sq, d), float32 out."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bs = block_size or sk
    m = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    acc = jnp.zeros((b, h, sq, d), jnp.float32)
    qf = q.astype(jnp.float32)
    for start in range(0, sk, bs):
        kb = k[:, :, start:start + bs].astype(jnp.float32)
        vb = v[:, :, start:start + bs]
        mask = _causal_mask(sq, kb.shape[2], q_offset,
                            k_offset + start) if causal else None
        m, l, acc = _stream_block(qf, kb, vb, m, l, acc, mask)
    l = jnp.maximum(l, 1e-30)
    return acc / l[..., None]


def unchecked_shard_map(f, mesh, in_specs, out_specs):
    """shard_map with the replication (varying-manual-axes) check off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def ring_attention(q, k, v, mesh, seq_axis: str, causal: bool = False):
    """Ring attention under shard_map.

    q,k,v: GLOBAL (B, H, S, d) arrays; ``mesh`` must contain ``seq_axis``
    (sequence shards) — other mesh axes may shard batch/heads and are passed
    through untouched.  Returns global (B, H, S, d) float32.
    """
    from jax.sharding import PartitionSpec as P

    axes = dict(mesh.shape)
    p = axes[seq_axis]
    if p == 1:
        return blockwise_attention(q, k, v, causal)

    # batch/head sharding: use 'n' / 'h' axes when present in the mesh
    n_ax = "n" if "n" in axes and axes["n"] > 1 else None
    h_ax = "h" if "h" in axes and axes["h"] > 1 else None
    spec = P(n_ax, h_ax, seq_axis, None)

    from flexflow_tpu.ops.pallas import flash_enabled

    use_flash = flash_enabled()

    def ring_kv(kl, vl, state, attend_step):
        """The ring protocol, shared by both bodies: K/V chunks rotate to
        the next neighbor each step; ``attend_step(t, kb, vb, state)``
        folds the resident chunk into the running state."""
        perm = [(i, (i + 1) % p) for i in range(p)]

        def step(carry, t):
            kb, vb, state = carry
            state = attend_step(t, kb, vb, state)
            kb = lax.ppermute(kb, seq_axis, perm)
            vb = lax.ppermute(vb, seq_axis, perm)
            return (kb, vb, state), 0.0

        (_, _, state), _ = lax.scan(step, (kl, vl, state), jnp.arange(p))
        return state

    def local_flash(ql, kl, vl):
        """Ring step body on the Pallas kernel: each step attends Q against
        the resident K/V chunk via flash_attention_partial and merges by
        log-sum-exp weight.  Causal masking never needs chunk offsets: a
        step is either fully visible (source chunk strictly behind this
        device's queries -> plain attention), diagonal (same chunk ->
        plain causal), or fully hidden (skip) — so the kernels stay
        offset-free and static."""
        from flexflow_tpu.ops.pallas.flash_attention import (
            combine_partials, flash_attention_partial)

        idx = lax.axis_index(seq_axis)
        b, h, sq, d = ql.shape

        def attend(t, kb, vb, state):
            o, lse = state
            src = (idx - t) % p  # whose chunk we currently hold
            if causal:
                def full_fn(args):
                    return flash_attention_partial(*args, causal=False)

                def diag_fn(args):
                    return flash_attention_partial(*args, causal=True)

                def masked_fn(args):
                    return (jnp.zeros((b, h, sq, d), jnp.float32),
                            jnp.full((b, h, sq), -jnp.inf, jnp.float32))

                branch = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
                o_t, lse_t = lax.switch(branch, [full_fn, diag_fn, masked_fn],
                                        (ql, kb, vb))
            else:
                o_t, lse_t = flash_attention_partial(ql, kb, vb, causal=False)
            return combine_partials(o, lse, o_t, lse_t)

        o, _ = ring_kv(kl, vl,
                       (jnp.zeros((b, h, sq, d), jnp.float32),
                        jnp.full((b, h, sq), -jnp.inf, jnp.float32)),
                       attend)
        return o

    def local(ql, kl, vl):
        s_local = ql.shape[2]
        idx = lax.axis_index(seq_axis)
        b, h, sq, d = ql.shape
        qf = ql.astype(jnp.float32)
        q_off = idx * s_local

        def attend(t, kb, vb, state):
            m, l, acc = state
            src = (idx - t) % p  # whose block we currently hold
            k_off = src * s_local
            mask = _causal_mask(sq, s_local, q_off, k_off) if causal else None
            return _stream_block(qf, kb.astype(jnp.float32), vb,
                                 m, l, acc, mask)

        m, l, acc = ring_kv(kl, vl,
                            (jnp.full((b, h, sq), -jnp.inf, jnp.float32),
                             jnp.zeros((b, h, sq), jnp.float32),
                             jnp.zeros((b, h, sq, d), jnp.float32)),
                            attend)
        l = jnp.maximum(l, 1e-30)
        return acc / l[..., None]

    body = local_flash if use_flash else local
    return unchecked_shard_map(body, mesh, (spec, spec, spec), spec)(q, k, v)
