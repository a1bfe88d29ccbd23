"""A hybrid linear-attention mixture-of-experts causal language model
(``model_type`` ``bailing_hybrid``; inclusionAI's Ling-3.0-flash is one):
pre-norm blocks whose mixer is Kimi Delta Attention (``ops/kda.py``) or,
at every ``layer_group_size``-th layer, latent attention, and whose
feed-forward is dense in the first ``first_k_dense_replace`` blocks and
an expert layer after them.  RMSNorm throughout, no bias, an untied head:

    h = x + Mixer_l(RMSNorm1(x));   y = h + FFN_l(RMSNorm2(h))

* ``Mixer_l``: latent attention (``blk<i>_mla``) where ``(i + 1) %
  layer_group_size == 0``, with an RMSNorm a head of q and k before the
  rotary turn (``use_qk_norm``) and a sigmoid gate a head and token on its
  result (``gated_attention_proj_granularity_type`` ``head_wise``); else
  KDA at ``num_attention_heads`` heads of ``head_dim``, causal
  convolutions of ``short_conv_kernel_size`` taps, decays bounded below by
  ``kda_lower_bound`` (``blk<i>_kda_in``, ``_scan``, ``_out``);
* ``FFN_l``: a gated SiLU feed-forward of ``intermediate_size``
  (``blk<i>_ffn``), or a sigmoid router over all ``router_outputs``
  experts with a selection bias, its top ``num_experts_per_tok`` chosen
  within the ``topk_group`` best of ``n_group`` groups, renormalised and
  scaled by ``routed_scaling_factor`` (``blk<i>_moe_router``), the experts
  this chip holds (``blk<i>_moe_experts``) and the shared expert
  (``blk<i>_moe_shared``).

Blocks ``0 .. num_layers - 1`` of the published ``num_hidden_layers`` are
built; every block is recomputed in the backward pass, as
``models/latent_moe.py`` says and for its reason.  The multi-token
prediction layer is not built: its loss is scaled by 0
(``mtp_loss_scaling_factor``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

from flexflow_tpu.config import FFConfig
from flexflow_tpu.machine import MachineModel
from flexflow_tpu.models.next_token import NextTokenLM, sgd_settings
from flexflow_tpu.strategy import Strategy


@dataclasses.dataclass
class LingHybridConfig:
    batch_size: int = 2
    seq_length: int = 64
    num_layers: int = 3                 # blocks built here, from layer 0
    layer_group_size: int = 3           # every this-th layer is MLA
    hidden_size: int = 64
    num_attention_heads: int = 4        # KDA's heads and MLA's alike
    head_dim: int = 16                  # a KDA head's key and value width
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 6e6
    intermediate_size: int = 128        # the dense blocks' feed-forward
    moe_intermediate_size: int = 32     # one routed expert's
    moe_shared_expert_intermediate_size: int = 32
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    router_outputs: int = 16            # experts of a layer, on all chips
    experts_held: Tuple[int, int] = (0, 16)
    num_experts_per_tok: int = 2
    n_group: int = 4
    topk_group: int = 2
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    vocab_size: int = 256
    embedding_std: float = 1.0
    bias_update_rate: float = 1e-3      # gamma of the selection bias
    rows_capacity_factor: float = 2.0
    learning_rate: float = 1e-3
    num_iterations: int = 10
    compute_dtype: str = "float32"
    param_dtype: str = "float32"
    seed: int = 0
    #: further FFConfig fields by name (obs_dir, ckpt_dir, ...)
    ff: Dict = dataclasses.field(default_factory=dict)

    def is_mla(self, layer: int) -> bool:
        return (layer + 1) % self.layer_group_size == 0

    @classmethod
    def from_config(cls, config: Dict, **overrides) -> "LingHybridConfig":
        """From a configuration file of the public ``config.json``'s keys
        (``benchmarks/configs/ling_3_0_flash.json`` is one); only the
        mechanisms this class builds are accepted, and a kept layer whose
        experts clamp their gate (a nonzero swiglu limit, whose form
        ``config.json`` does not give) is refused."""
        want = {"scoring_func": "sigmoid", "score_function": "sigmoid",
                "topk_method": "noaux_tc", "norm_topk_prob": True,
                "moe_router_enable_expert_bias": True,
                "scale_router_input": False, "hidden_act": "silu",
                "q_lora_rank": None, "use_bias": False,
                "use_qkv_bias": False, "tie_word_embeddings": False,
                "use_qk_norm": True, "rope_interleave": True,
                "rope_scaling": None, "use_mla_nope": False,
                "gated_attention_proj_granularity_type": "head_wise",
                "kda_safe_gate": True, "no_kda_lora": True,
                "use_kda_lora": False, "linear_silu": True,
                "group_norm_size": 1, "num_kv_heads_for_linear_attn": 0,
                "value_norm": False, "up_proj_norm": False,
                "use_nGPT": False, "mtp_loss_scaling_factor": 0}
        for key, value in want.items():
            if config.get(key, value) != value:
                raise ValueError(f"{key} = {config[key]!r}: this model "
                                 f"class builds {value!r} only")
        if config["num_key_value_heads"] != config["num_attention_heads"]:
            raise ValueError("latent attention has one key a head")
        rope = int(config["qk_rope_head_dim"])
        if int(config.get("rotary_dim", rope)) != rope or int(config.get(
                "qk_head_dim", rope + int(config["qk_nope_head_dim"]))) \
                != rope + int(config["qk_nope_head_dim"]):
            raise ValueError("rotary_dim and qk_head_dim disagree with "
                             "qk_rope_head_dim and qk_nope_head_dim")
        layers = int(config.get("num_layers", config["num_hidden_layers"]))
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            limits = config.get(key, [0] * layers)[:layers]
            if any(limits):
                raise ValueError(
                    f"{key} {limits}: a clamped expert gate in layers "
                    f"0-{layers - 1}, whose form config.json does not give")
        held = tuple(int(v) for v in config.get(
            "experts_held", (0, config["num_experts"])))
        own = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in own
              and k not in ("ff", "learning_rate")}
        kw.update(num_layers=layers, experts_held=held,
                  router_outputs=int(config.get("router_outputs",
                                                config["num_experts"])))
        kw.update(sgd_settings(config))
        kw.update(overrides)
        return cls(**kw)


class LingHybridLM(NextTokenLM):
    def __init__(self, t_config: LingHybridConfig = None,
                 machine: Optional[MachineModel] = None,
                 strategies: Optional[Strategy] = None):
        self.t = t = t_config or LingHybridConfig()
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
            h = self.rms_norm(f"blk{i}_norm1", x, t.rms_norm_eps)
            if t.is_mla(i):
                h = self.latent_attention(
                    f"blk{i}_mla", h, t.num_attention_heads, t.kv_lora_rank,
                    t.qk_nope_head_dim, t.qk_rope_head_dim, t.v_head_dim,
                    t.rope_theta, t.rms_norm_eps, qk_norm=t.rms_norm_eps,
                    head_gate=True)
            else:
                h = self.kda_mixer(
                    f"blk{i}_kda", h, t.num_attention_heads, t.head_dim,
                    t.short_conv_kernel_size, t.kda_lower_bound,
                    t.rms_norm_eps)
            x = self.add_seq(f"blk{i}_res1", x, h)
            h = self.rms_norm(f"blk{i}_norm2", x, t.rms_norm_eps)
            if i < t.first_k_dense_replace:
                h = self.gated_ffn(f"blk{i}_ffn", h, t.intermediate_size)
            else:
                gates = self.top_k_router(
                    f"blk{i}_moe_router", h, t.router_outputs,
                    t.num_experts_per_tok, t.routed_scaling_factor,
                    t.bias_update_rate, score="sigmoid", n_group=t.n_group,
                    topk_group=t.topk_group)
                routed = self.held_experts(
                    f"blk{i}_moe_experts", h, gates,
                    t.moe_intermediate_size, t.experts_held,
                    t.num_experts_per_tok, t.rows_capacity_factor)
                shared = self.gated_ffn(
                    f"blk{i}_moe_shared", h, t.num_shared_experts
                    * t.moe_shared_expert_intermediate_size)
                h = self.add_seq(f"blk{i}_moe_sum", routed, shared)
            x = self.add_seq(f"blk{i}_res2", x, h)
            self.recompute_blocks.append(range(first, len(self.layers)))
        x = self.rms_norm("final_norm", x, t.rms_norm_eps)
        logits = self.seq_linear("lm_head", x, t.vocab_size, use_bias=False)
        self.softmax_seq("softmax", logits, self.labels)
        self.loss_op = self.layers[-1]
