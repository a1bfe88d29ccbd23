"""Embedding (reference: nmt/embed.cu — custom gather forward kernel
:151-165, scatter-add backward via atomicAdd :167-180).

TPU-native: ``jnp.take`` on the table; the scatter-add backward is jax's
gather VJP.  1-D grid over batch.  The reference requires power-of-2
output_size (shift arithmetic in its kernels) — no such restriction here.
Chunk ops share one table via param_key (srcEmbed/dstEmbed SharedVariables,
nmt/rnn.cu:159-194), and so does the output head of a model whose word
embeddings are tied (:class:`TiedHead`): one parameter receives the
scatter-add of the token gradients and the head's weight gradient."""

from __future__ import annotations

from typing import Dict, List

from flexflow_tpu.ops.base import Op, Tensor
from flexflow_tpu.ops.rnn_linear import RnnLinear
from flexflow_tpu.strategy import ParallelConfig


class Embed(Op):
    AXIS_NAMES = ("n",)

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 vocab_size: int, embed_size: int,
                 param_key: str = None, compute_dtype: str = "float32",
                 init_std: float = 0.05, multiplier: float = 1.0):
        super().__init__(name, pc, [input])
        assert input.ndim == 2, "embed input must be (batch, length) int ids"
        self.vocab_size = vocab_size
        self.embed_size = embed_size
        # token models have no float graph input to cast, so the model's
        # compute_dtype is applied HERE, at the source of the float path —
        # every downstream seq op follows x.dtype (the CNN path's analog
        # is make_train_step's image.astype)
        self.compute_dtype = compute_dtype
        self.init_std = float(init_std)
        #: what the looked-up rows are multiplied by (a published
        #: ``embedding_multiplier``); 1 leaves them as they are
        self.multiplier = float(multiplier)
        if param_key:
            self.param_key = param_key
        n, length = input.shape
        self.output = Tensor((n, length, embed_size), compute_dtype, self,
                             name)

    def init_params(self, rng) -> Dict:
        import jax

        # small normal like the reference's rnn_randomize by default; a
        # model whose blocks renormalise their input may ask for more
        table = jax.random.normal(
            rng, (self.vocab_size, self.embed_size), "float32") \
            * self.init_std
        return {"table": table}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {"table": P(None, None)}

    def output_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("n", None, None)

    def input_specs(self, pc=None):
        from jax.sharding import PartitionSpec as P

        return [P("n", None)]

    def regrid_input_specs(self):
        from jax.sharding import PartitionSpec as P

        return [P("n", None)]

    def placement_signature(self):
        # embeds pinned to distinct devices (the reference's explicit
        # GPU-0/1 placement, nmt/nmt.cc:273-299) group when table geometry
        # matches
        return (self.vocab_size, self.embed_size, self.compute_dtype)

    def forward(self, params, state, xs: List, train: bool):
        import jax.numpy as jnp

        (ids,) = xs
        # gather first, cast after: avoids materializing a whole-vocab
        # low-precision table copy, and the autodiff transpose (scatter-
        # add of token gradients) then accumulates in the table's f32
        rows = jnp.take(params["table"], ids, axis=0)
        if self.multiplier != 1.0:
            rows = rows * self.multiplier
        return rows.astype(self.compute_dtype), state

    def param_bytes(self) -> int:
        return 4 * self.vocab_size * self.embed_size


class TiedHead(RnnLinear):
    """The vocabulary projection of a model whose word embeddings are tied
    (``tie_word_embeddings``): ``logits = x table^T / logits_scaling`` with
    ``table`` the embedding's own matrix, found under the embedding's
    ``param_key``; no bias.  Followed by the loss it takes the fused
    projection+CE kernel as any :class:`RnnLinear` does
    (``FFModel._lm_head_fusion``)."""

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 embed: Embed, logits_scaling: float = 1.0):
        super().__init__(name, pc, input, embed.vocab_size,
                         param_key=embed.param_key, use_bias=False)
        assert input.shape[2] == embed.embed_size
        self.embed = embed
        self.logits_scaling = float(logits_scaling)

    def init_params(self, rng) -> Dict:
        return self.embed.init_params(rng)

    def param_specs(self):
        return self.embed.param_specs()

    def head_operands(self, params, x):
        if self.logits_scaling != 1.0:
            x = (x * (1.0 / self.logits_scaling)).astype(x.dtype)
        return x, params["table"].T

    def local_clone(self, pc: ParallelConfig):
        return None

    def param_bytes(self) -> int:
        return 0        # the embedding's: counted there
