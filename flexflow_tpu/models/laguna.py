"""A causal language model of sliding-window and full attention layers
mixed by a per-layer list, over a softmax top-k expert layer
(``model_type`` ``laguna``; poolside's Laguna-S-2.1 is one).  Pre-norm
blocks, RMSNorm throughout, no bias, an untied head:

    h <- h + Attn_l(RMSNorm(h));   h <- h + FF_l(RMSNorm(h))

Everything that differs from layer to layer is read from the public
``config.json``'s own per-layer lists, at the entries of the layers built
here (``num_layers`` of the published ``num_hidden_layers``):

* ``layer_types[l]``: ``full_attention`` or ``sliding_attention``
  (a query sees itself and the ``sliding_window - 1`` keys before it),
  each with its own rotary rule in ``rope_parameters`` (theta, default or
  YaRN, ``partial_rotary_factor`` of the head turned);
* ``num_attention_heads_per_layer[l]`` query heads of ``head_dim`` on the
  model's ``num_key_value_heads``, and a sigmoid gate a head and token on
  the attention's result (``gating`` ``per-head``);
* ``mlp_layer_types[l]``: ``dense`` (a gated SiLU feed-forward of
  ``intermediate_size``) or ``sparse``: a router over ``router_outputs``
  experts (softmax over all of them, the ``num_experts_per_tok`` largest,
  renormalised, times ``moe_routed_scaling_factor``), the ``num_experts``
  of them this chip holds (``experts_held``; ops/expert_share.py) and one
  shared expert on every token, added without a gate.

The operators are one kind of attention (``GroupedQueryAttention``, named
``blk<i>_attn_full`` or ``blk<i>_attn_window``), ``blk<i>_ffn`` or
``blk<i>_moe_router`` / ``_moe_experts`` / ``_moe_shared``.  Every block
is recomputed in the backward pass, as ``models/latent_moe.py`` says and
for its reason.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

from flexflow_tpu.config import FFConfig
from flexflow_tpu.machine import MachineModel
from flexflow_tpu.models.next_token import NextTokenLM, sgd_settings
from flexflow_tpu.strategy import Strategy

LAYER_TYPES = ("full_attention", "sliding_attention")
MLP_LAYER_TYPES = ("dense", "sparse")

_DEFAULT_ROPE = {"rope_type": "default", "rope_theta": 10000.0,
                 "partial_rotary_factor": 1.0}


@dataclasses.dataclass
class LagunaConfig:
    batch_size: int = 2
    seq_length: int = 64
    num_layers: int = 2                 # blocks built here: entries 0.. of
    layer_types: Tuple[str, ...] = ("full_attention", "sliding_attention")
    mlp_layer_types: Tuple[str, ...] = ("dense", "sparse")
    num_attention_heads_per_layer: Tuple[int, ...] = (4, 6)
    hidden_size: int = 64
    num_key_value_heads: int = 2
    head_dim: int = 16
    sliding_window: int = 8
    rope_parameters: Dict = dataclasses.field(default_factory=lambda: {
        k: dict(_DEFAULT_ROPE) for k in LAYER_TYPES})
    intermediate_size: int = 128        # the dense blocks' feed-forward
    moe_intermediate_size: int = 32     # one routed expert's
    shared_expert_intermediate_size: int = 32
    router_outputs: int = 8             # experts of a layer, on all chips
    experts_held: Tuple[int, int] = (0, 8)
    num_experts_per_tok: int = 2
    moe_routed_scaling_factor: float = 1.0
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

    @classmethod
    def from_config(cls, config: Dict, **overrides) -> "LagunaConfig":
        """From a configuration file of the public ``config.json``'s keys
        (``benchmarks/configs/laguna_s_2_1.json`` is one); only the
        mechanisms this class builds are accepted."""
        want = {"attention_bias": False, "norm_topk_prob": True,
                "decoder_sparse_step": 1, "tie_word_embeddings": False,
                "gating": "per-head",
                "moe_apply_router_weight_on_input": False,
                "moe_router_logit_softcapping": 0}
        for key, value in want.items():
            if config.get(key, value) != value:
                raise ValueError(f"{key} = {config[key]!r}: this model "
                                 f"class builds {value!r} only")
        layers = int(config.get("num_layers", config["num_hidden_layers"]))
        lists = {k: tuple(config[k]) for k in (
            "layer_types", "mlp_layer_types",
            "num_attention_heads_per_layer")}
        for key, values in lists.items():
            if len(values) < layers:
                raise ValueError(f"{len(values)} {key} for {layers} layers")
        gates = set(config.get("gating_types", ())[:layers])
        if gates - {"per_head"}:
            raise ValueError(f"gating_types {sorted(gates)}: this model "
                             f"class builds 'per_head' only")
        dense = {i for i, kind in enumerate(lists["mlp_layer_types"])
                 if kind == "dense"}
        if "mlp_only_layers" in config \
                and set(config["mlp_only_layers"]) != dense:
            raise ValueError(f"mlp_only_layers {config['mlp_only_layers']} "
                             f"against dense mlp_layer_types at "
                             f"{sorted(dense)}")
        held = tuple(int(v) for v in config.get(
            "experts_held", (0, config["num_experts"])))
        if held[1] - held[0] != int(config["num_experts"]):
            raise ValueError(f"experts_held {list(held)} against "
                             f"num_experts {config['num_experts']}")
        own = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in own
              and k not in ("ff", "learning_rate") and k not in lists}
        kw.update(lists, num_layers=layers, experts_held=held,
                  router_outputs=int(config.get("router_outputs",
                                                config["num_experts"])))
        kw.update(sgd_settings(config))
        kw.update(overrides)
        return cls(**kw)

    def rope_rule(self, layer_type: str) -> Dict:
        """The rotary rule of a layer type as ``ops/seq_gated.rotary_table``
        reads it: the published group and the dimensions that turn."""
        rule = dict(_DEFAULT_ROPE, **self.rope_parameters[layer_type])
        rule["dim"] = int(self.head_dim
                          * float(rule.pop("partial_rotary_factor")))
        return rule


class LagunaLM(NextTokenLM):
    def __init__(self, t_config: LagunaConfig = None,
                 machine: Optional[MachineModel] = None,
                 strategies: Optional[Strategy] = None):
        self.t = t = t_config or LagunaConfig()
        for key, known in (("layer_types", LAYER_TYPES),
                           ("mlp_layer_types", MLP_LAYER_TYPES)):
            unknown = set(getattr(t, key)[:t.num_layers]) - set(known)
            if unknown:
                raise ValueError(f"{key} {sorted(unknown)}: one of {known}")
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
        self.recompute_blocks = []
        for i in range(t.num_layers):
            first = len(self.layers)
            windowed = t.layer_types[i] == "sliding_attention"
            h = self.rms_norm(f"blk{i}_norm1", x, t.rms_norm_eps)
            h = self.grouped_query_attention(
                f"blk{i}_attn_{'window' if windowed else 'full'}", h,
                t.num_attention_heads_per_layer[i], t.num_key_value_heads,
                t.head_dim, t.head_dim ** -0.5,
                rope=t.rope_rule(t.layer_types[i]),
                window=t.sliding_window if windowed else None, gate=True)
            x = self.add_seq(f"blk{i}_res1", x, h)
            h = self.rms_norm(f"blk{i}_norm2", x, t.rms_norm_eps)
            if t.mlp_layer_types[i] == "dense":
                h = self.gated_ffn(f"blk{i}_ffn", h, t.intermediate_size)
            else:
                gates = self.top_k_router(
                    f"blk{i}_moe_router", h, t.router_outputs,
                    t.num_experts_per_tok, t.moe_routed_scaling_factor,
                    score="softmax")
                routed = self.held_experts(
                    f"blk{i}_moe_experts", h, gates,
                    t.moe_intermediate_size, t.experts_held,
                    t.num_experts_per_tok, t.rows_capacity_factor)
                shared = self.gated_ffn(
                    f"blk{i}_moe_shared", h,
                    t.shared_expert_intermediate_size)
                h = self.add_seq(f"blk{i}_moe_sum", routed, shared)
            x = self.add_seq(f"blk{i}_res2", x, h)
            self.recompute_blocks.append(range(first, len(self.layers)))
        x = self.rms_norm("final_norm", x, t.rms_norm_eps)
        logits = self.seq_linear("lm_head", x, t.vocab_size, use_bias=False)
        self.softmax_seq("softmax", logits, self.labels)
        self.loss_op = self.layers[-1]
