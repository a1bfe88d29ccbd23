"""A DeepSeek-V3-style causal language model (``model_type``
``deepseek_v3``; Moonlight-16B-A3B is one): pre-norm blocks of latent
attention and a gated feed-forward, the first ``first_k_dense_replace``
of them dense and the rest expert layers (a sigmoid router over all the
layer's experts, the experts this chip holds, the shared experts), RMSNorm
throughout, rotary positions inside the attention, an untied head without
bias.  The field names are the public ``config.json``'s.

One chip's share of a deployment is a configuration like any other:
``experts_held`` names the routed experts that live here (the router
keeps all ``router_outputs``), ``vocab_size`` the rows of the vocabulary.
The part of an expert layer's result that other chips' experts would add
is left out, as ``ops/expert_share.py`` says.

Every block is recomputed in the backward pass (``recompute_blocks``):
the block boundaries are kept, which is what lets 8192-token sequences
through at all, and with them what the kernels name as dearer to make
again than to hold (``ops/pallas.KEPT_RESULTS``: the flash forward's
``out`` and ``lse``, 68 MB a layer at 2 x 8192 tokens, which the flash
backward reads and only a second run of the kernel, 6.84 ms, would give
back; PERF.md section 6, PR 29).  Norms, projections, the rotary, the
router and both kinds of experts run once more.  The unit and its
placement are this class's and no option's.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

from flexflow_tpu.config import FFConfig
from flexflow_tpu.machine import MachineModel
from flexflow_tpu.models.next_token import NextTokenLM, sgd_settings
from flexflow_tpu.strategy import Strategy


@dataclasses.dataclass
class LatentMoEConfig:
    batch_size: int = 2
    seq_length: int = 64
    num_layers: int = 2                 # blocks built here, dense ones first
    hidden_size: int = 64
    num_attention_heads: int = 4
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    intermediate_size: int = 128        # the dense blocks' feed-forward
    moe_intermediate_size: int = 32     # one expert's
    first_k_dense_replace: int = 1
    router_outputs: int = 8             # experts of a layer, on all chips
    experts_held: Tuple[int, int] = (0, 8)
    num_experts_per_tok: int = 2
    n_shared_experts: int = 2
    routed_scaling_factor: float = 1.0
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    vocab_size: int = 256
    bias_update_rate: float = 1e-3      # gamma of the selection bias
    rows_capacity_factor: float = 2.0
    learning_rate: float = 1e-3
    num_iterations: int = 10
    compute_dtype: str = "float32"
    param_dtype: str = "float32"
    seed: int = 0
    #: further FFConfig fields by name (obs_dir, ckpt_dir, ...)
    ff: Dict = dataclasses.field(default_factory=dict)

    @classmethod
    def from_config(cls, config: Dict, **overrides) -> "LatentMoEConfig":
        """From a configuration file of the public ``config.json``'s keys
        (``benchmarks/configs/moonlight_16b_a3b.json`` is one); only the
        mechanisms this class builds are accepted."""
        want = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
                "norm_topk_prob": True, "hidden_act": "silu",
                "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
                "q_lora_rank": None, "attention_bias": False,
                "tie_word_embeddings": False,
                "num_nextn_predict_layers": 0}
        for key, value in want.items():
            if config.get(key, value) != value:
                raise ValueError(f"{key} = {config[key]!r}: this model "
                                 f"class builds {value!r} only")
        if config["num_key_value_heads"] != config["num_attention_heads"]:
            raise ValueError("latent attention has one key a head")
        own = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in own
              and k not in ("ff", "learning_rate")}
        kw["experts_held"] = tuple(config["experts_held"])
        kw.update(sgd_settings(config))
        kw.update(overrides)
        return cls(**kw)


class LatentMoELM(NextTokenLM):
    def __init__(self, t_config: LatentMoEConfig = None,
                 machine: Optional[MachineModel] = None,
                 strategies: Optional[Strategy] = None):
        self.t = t = t_config or LatentMoEConfig()
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
        # unit-variance embeddings (nn.Embedding's default): at the 0.05
        # of ops/embed.py a random model's residual stream is all block
        # output, the same for every token, and every router collapses
        # onto a few experts (load max/mean 2-4.6 where this gives 1.2-1.5)
        x = self.embed("embed", self.tokens, t.vocab_size, t.hidden_size,
                       init_std=1.0)
        self.recompute_blocks = []
        for i in range(t.num_layers):
            first = len(self.layers)
            h = self.rms_norm(f"blk{i}_norm1", x, t.rms_norm_eps)
            h = self.latent_attention(
                f"blk{i}_mla", h, t.num_attention_heads, t.kv_lora_rank,
                t.qk_nope_head_dim, t.qk_rope_head_dim, t.v_head_dim,
                t.rope_theta, t.rms_norm_eps)
            x = self.add_seq(f"blk{i}_res1", x, h)
            h = self.rms_norm(f"blk{i}_norm2", x, t.rms_norm_eps)
            if i < t.first_k_dense_replace:
                h = self.gated_ffn(f"blk{i}_ffn", h, t.intermediate_size)
            else:
                gates = self.top_k_router(
                    f"blk{i}_moe_router", h, t.router_outputs,
                    t.num_experts_per_tok, t.routed_scaling_factor,
                    t.bias_update_rate, score="sigmoid")
                routed = self.held_experts(
                    f"blk{i}_moe_experts", h, gates,
                    t.moe_intermediate_size, t.experts_held,
                    t.num_experts_per_tok, t.rows_capacity_factor)
                shared = self.gated_ffn(
                    f"blk{i}_moe_shared", h,
                    t.n_shared_experts * t.moe_intermediate_size)
                h = self.add_seq(f"blk{i}_moe_sum", routed, shared)
            x = self.add_seq(f"blk{i}_res2", x, h)
            self.recompute_blocks.append(range(first, len(self.layers)))
        x = self.rms_norm("final_norm", x, t.rms_norm_eps)
        logits = self.seq_linear("lm_head", x, t.vocab_size, use_bias=False)
        self.softmax_seq("softmax", logits, self.labels)
        self.loss_op = self.layers[-1]
