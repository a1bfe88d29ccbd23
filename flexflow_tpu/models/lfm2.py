"""A causal language model of gated short convolutions and grouped-query
attention layers mixed by a per-layer list, over leading dense
feed-forwards and then a sigmoid top-k expert layer with a selection bias
(``model_type`` ``lfm2_moe``; LiquidAI's LFM2-8B-A1B is one).  Pre-norm
blocks, RMSNorm throughout, no bias anywhere, a tied head:

    h <- h + Op_l(RMSNorm(h));   h <- h + FF_l(RMSNorm(h))
    logits = RMSNorm(h) E^T

* ``layer_types[l]``: ``conv`` (``ops/short_conv.py``: ``[B | C | X] = x
  W_in``, a depthwise causal convolution of ``conv_L_cache`` taps over ``B
  * X``, ``(C * v) W_out``) or ``full_attention``
  (``GroupedQueryAttention`` under ``qk_norm`` and the default rotary rule
  at ``rope_theta`` over the whole head, scale ``head_dim ** -0.5``; no
  gate, no window);
* ``FF_l``: a gated SiLU feed-forward of ``intermediate_size`` in the
  first ``num_dense_layers`` blocks; in the others a router over
  ``router_outputs`` experts (sigmoid scores, the ``num_experts_per_tok``
  largest of ``score + bias``, weights ``score / (sum of the selected +
  1e-6)`` times ``routed_scaling_factor``; the bias is state, moved at the
  router's own rate: ``ops/expert_share.py``) and the ``num_experts`` of them this chip holds
  (``experts_held``), each SiLU-gated at ``moe_intermediate_size``.  No
  shared expert.

The operators are named ``blk<i>_conv`` or ``blk<i>_attn_full``,
``blk<i>_ffn`` or ``blk<i>_moe_router`` / ``_moe_experts``.  Blocks
``0 .. num_layers - 1`` of the published ``num_hidden_layers`` are built.
Every block is recomputed in the backward pass, as ``models/latent_moe.py``
says and for its reason.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

from flexflow_tpu.config import FFConfig
from flexflow_tpu.machine import MachineModel
from flexflow_tpu.models.next_token import NextTokenLM, sgd_settings
from flexflow_tpu.strategy import Strategy

LAYER_TYPES = ("conv", "full_attention")
#: under the sum of the selected scores, as the published router has it
ROUTER_DENOMINATOR_EPS = 1e-6


@dataclasses.dataclass
class Lfm2Config:
    batch_size: int = 2
    seq_length: int = 64
    num_layers: int = 3                 # blocks built here: entries 0.. of
    layer_types: Tuple[str, ...] = ("conv", "full_attention", "conv")
    num_dense_layers: int = 1
    hidden_size: int = 64
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    conv_L_cache: int = 3               # taps of the short convolution
    rope_theta: float = 1e6
    intermediate_size: int = 128        # the dense blocks' feed-forward
    moe_intermediate_size: int = 32     # one routed expert's
    router_outputs: int = 8             # experts of a layer, on all chips
    experts_held: Tuple[int, int] = (0, 8)
    num_experts_per_tok: int = 2
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    vocab_size: int = 256
    embedding_std: float = 1.0
    rows_capacity_factor: float = 2.0
    learning_rate: float = 1e-3
    num_iterations: int = 10
    compute_dtype: str = "float32"
    param_dtype: str = "float32"
    seed: int = 0
    #: further FFConfig fields by name (obs_dir, ckpt_dir, ...)
    ff: Dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        kinds = self.layer_types[:self.num_layers]
        unknown = set(kinds) - set(LAYER_TYPES)
        if unknown or len(kinds) < self.num_layers:
            raise ValueError(f"layer_types {list(kinds)} for "
                             f"{self.num_layers} layers of {LAYER_TYPES}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_config(cls, config: Dict, **overrides) -> "Lfm2Config":
        """From a configuration file of the public ``config.json``'s keys
        (``benchmarks/configs/lfm2_8b_a1b.json`` is one); only the
        mechanisms this class builds are accepted."""
        want = {"conv_bias": False, "norm_topk_prob": True,
                "use_expert_bias": True, "tie_word_embeddings": True}
        for key, value in want.items():
            if config.get(key, value) != value:
                raise ValueError(f"{key} = {config[key]!r}: this model "
                                 f"class builds {value!r} only")
        types = tuple(config["layer_types"])
        layers = int(config.get("num_layers", config["num_hidden_layers"]))
        if int(config["hidden_size"]) % int(config["num_attention_heads"]):
            raise ValueError("num_attention_heads does not divide "
                             "hidden_size")
        held = tuple(int(v) for v in config.get(
            "experts_held", (0, config["num_experts"])))
        if held[1] - held[0] != int(config["num_experts"]):
            raise ValueError(f"experts_held {list(held)} against "
                             f"num_experts {config['num_experts']}")
        own = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in own
              and k not in ("ff", "learning_rate", "layer_types")}
        kw.update(layer_types=types, num_layers=layers, experts_held=held,
                  router_outputs=int(config.get("router_outputs",
                                                config["num_experts"])))
        kw.update(sgd_settings(config))
        kw.update(overrides)
        return cls(**kw)


class Lfm2LM(NextTokenLM):
    def __init__(self, t_config: Lfm2Config = None,
                 machine: Optional[MachineModel] = None,
                 strategies: Optional[Strategy] = None):
        self.t = t = t_config or Lfm2Config()
        super().__init__(FFConfig(
            batch_size=t.batch_size, learning_rate=t.learning_rate,
            weight_decay=0.0, num_iterations=t.num_iterations,
            compute_dtype=t.compute_dtype, param_dtype=t.param_dtype,
            seed=t.seed, strategies=strategies or Strategy(), **t.ff),
            machine)
        self._build()

    def _build(self):
        t = self.t
        self.tokens = self.create_input((t.batch_size, t.seq_length),
                                        "int32", "tokens")
        self.labels = self.create_input((t.batch_size, t.seq_length),
                                        "int32", "labels")
        embedded = x = self.embed("embed", self.tokens, t.vocab_size,
                                  t.hidden_size, init_std=t.embedding_std)
        rope = {"rope_type": "default", "rope_theta": t.rope_theta,
                "dim": t.head_dim}
        self.recompute_blocks = []
        for i in range(t.num_layers):
            first = len(self.layers)
            h = self.rms_norm(f"blk{i}_norm1", x, t.norm_eps)
            if t.layer_types[i] == "conv":
                h = self.gated_short_conv(f"blk{i}_conv", h, t.conv_L_cache)
            else:
                h = self.grouped_query_attention(
                    f"blk{i}_attn_full", h, t.num_attention_heads,
                    t.num_key_value_heads, t.head_dim, t.head_dim ** -0.5,
                    rope=rope, qk_norm=t.norm_eps)
            x = self.add_seq(f"blk{i}_res1", x, h)
            h = self.rms_norm(f"blk{i}_norm2", x, t.norm_eps)
            if i < t.num_dense_layers:
                h = self.gated_ffn(f"blk{i}_ffn", h, t.intermediate_size)
            else:
                gates = self.top_k_router(
                    f"blk{i}_moe_router", h, t.router_outputs,
                    t.num_experts_per_tok, t.routed_scaling_factor,
                    score="sigmoid",
                    denominator_eps=ROUTER_DENOMINATOR_EPS)
                h = self.held_experts(
                    f"blk{i}_moe_experts", h, gates,
                    t.moe_intermediate_size, t.experts_held,
                    t.num_experts_per_tok, t.rows_capacity_factor)
            x = self.add_seq(f"blk{i}_res2", x, h)
            self.recompute_blocks.append(range(first, len(self.layers)))
        x = self.rms_norm("final_norm", x, t.norm_eps)
        logits = self.tied_head("lm_head", x, embedded)
        self.softmax_seq("softmax", logits, self.labels)
        self.loss_op = self.layers[-1]
