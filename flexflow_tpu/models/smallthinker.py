"""A causal language model whose every block routes its tokens before
attention: grouped-query attention layers, full without rotary positions
(NoPE) or windowed with them, as two per-layer lists say, each beside a
softmax top-k expert layer of ReGLU experts whose router reads the block's
own normed input (PowerInfer's SmallThinker-21BA3B-Instruct is one; its
``config.json`` has a ``model_name`` and no ``model_type``).  RMSNorm
throughout, no bias, no shared expert, no dense layer, an untied head:

    n = RMSNorm1(x);   gates = Router(n)
    h = x + Attn_l(n);   y = h + Experts_l(RMSNorm2(h), gates)

* ``sliding_window_layout[l]``: 1, a query sees itself and the
  ``sliding_window_size - 1`` keys before it (``blk<i>_attn_window``); 0,
  every earlier key (``blk<i>_attn_full``);
* ``rope_layout[l]``: 1, the default rotary rule at ``rope_theta`` over
  the whole head on q and k; 0, no positions at all;
* ``num_attention_heads`` query heads of ``head_dim`` on
  ``num_key_value_heads``, scale ``head_dim ** -0.5``;
* the router: softmax over all ``router_outputs`` logits
  (``moe_primary_router_apply_softmax``), the
  ``moe_num_active_primary_experts`` largest, renormalised
  (``norm_topk_prob``), unscaled, read from ``n`` before attention (its
  gradient returns through ``norm1``; ``moe.router_input.block``); the
  ``moe_num_primary_experts`` of them this chip holds (``experts_held``)
  each ``Wdown(relu(m Wgate) * (m Wup))`` at ``moe_ffn_hidden_size``.

The operators are ``blk<i>_moe_router``, ``blk<i>_attn_full`` or
``_attn_window`` and ``blk<i>_moe_experts``.  Blocks ``0 .. num_layers -
1`` of the published ``num_hidden_layers`` are built; every block is
recomputed in the backward pass, as ``models/latent_moe.py`` says and for
its reason.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

from flexflow_tpu.config import FFConfig
from flexflow_tpu.machine import MachineModel
from flexflow_tpu.models.next_token import NextTokenLM, sgd_settings
from flexflow_tpu.strategy import Strategy

#: the experts' gate (``described_as``: "sparse ReGLU")
EXPERT_ACT = "relu"


@dataclasses.dataclass
class SmallThinkerConfig:
    batch_size: int = 2
    seq_length: int = 64
    num_layers: int = 2                 # blocks built here: entries 0.. of
    sliding_window_layout: Tuple[int, ...] = (0, 1)
    rope_layout: Tuple[int, ...] = (0, 1)
    hidden_size: int = 64
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    sliding_window_size: int = 8
    rope_theta: float = 1.5e6
    moe_ffn_hidden_size: int = 32       # one routed expert's
    router_outputs: int = 8             # experts of a layer, on all chips
    experts_held: Tuple[int, int] = (0, 8)
    moe_num_active_primary_experts: int = 2
    rms_norm_eps: float = 1e-6
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
        for key in ("sliding_window_layout", "rope_layout"):
            flags = tuple(getattr(self, key))[:self.num_layers]
            if len(flags) < self.num_layers or set(flags) - {0, 1}:
                raise ValueError(f"{key} {list(flags)}: a 0 or 1 for each "
                                 f"of {self.num_layers} layers")

    @classmethod
    def from_config(cls, config: Dict, **overrides) -> "SmallThinkerConfig":
        """From a configuration file of the public ``config.json``'s keys
        (``benchmarks/configs/smallthinker_21b_a3b.json`` is one); only
        the mechanisms this class builds are accepted."""
        want = {"moe_primary_router_apply_softmax": True,
                "norm_topk_prob": True, "tie_word_embeddings": False,
                "rope_scaling": None}
        for key, value in want.items():
            if config.get(key, value) != value:
                raise ValueError(f"{key} = {config[key]!r}: this model "
                                 f"class builds {value!r} only")
        held = tuple(int(v) for v in config.get(
            "experts_held", (0, config["moe_num_primary_experts"])))
        if held[1] - held[0] != int(config["moe_num_primary_experts"]):
            raise ValueError(f"experts_held {list(held)} against "
                             f"moe_num_primary_experts "
                             f"{config['moe_num_primary_experts']}")
        layouts = ("sliding_window_layout", "rope_layout")
        own = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in own
              and k not in ("ff", "learning_rate") + layouts}
        kw.update({k: tuple(int(v) for v in config[k]) for k in layouts})
        kw.update(num_layers=int(config.get("num_layers",
                                            config["num_hidden_layers"])),
                  experts_held=held, router_outputs=int(config.get(
                      "router_outputs", config["moe_num_primary_experts"])))
        kw.update(sgd_settings(config))
        kw.update(overrides)
        return cls(**kw)


class SmallThinkerLM(NextTokenLM):
    def __init__(self, t_config: SmallThinkerConfig = None,
                 machine: Optional[MachineModel] = None,
                 strategies: Optional[Strategy] = None):
        self.t = t = t_config or SmallThinkerConfig()
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
        x = self.embed("embed", self.tokens, t.vocab_size, t.hidden_size,
                       init_std=t.embedding_std)
        rope = {"rope_type": "default", "rope_theta": t.rope_theta,
                "dim": t.head_dim}
        self.recompute_blocks = []
        for i in range(t.num_layers):
            first = len(self.layers)
            windowed = bool(t.sliding_window_layout[i])
            n = self.rms_norm(f"blk{i}_norm1", x, t.rms_norm_eps)
            gates = self.top_k_router(
                f"blk{i}_moe_router", n, t.router_outputs,
                t.moe_num_active_primary_experts, 1.0, score="softmax")
            h = self.grouped_query_attention(
                f"blk{i}_attn_{'window' if windowed else 'full'}", n,
                t.num_attention_heads, t.num_key_value_heads, t.head_dim,
                t.head_dim ** -0.5, rope=rope if t.rope_layout[i] else None,
                window=t.sliding_window_size if windowed else None)
            x = self.add_seq(f"blk{i}_res1", x, h)
            h = self.rms_norm(f"blk{i}_norm2", x, t.rms_norm_eps)
            h = self.held_experts(
                f"blk{i}_moe_experts", h, gates, t.moe_ffn_hidden_size,
                t.experts_held, t.moe_num_active_primary_experts,
                t.rows_capacity_factor, act=EXPERT_ACT)
            x = self.add_seq(f"blk{i}_res2", x, h)
            self.recompute_blocks.append(range(first, len(self.layers)))
        x = self.rms_norm("final_norm", x, t.rms_norm_eps)
        logits = self.seq_linear("lm_head", x, t.vocab_size, use_bias=False)
        self.softmax_seq("softmax", logits, self.labels)
        self.loss_op = self.layers[-1]
