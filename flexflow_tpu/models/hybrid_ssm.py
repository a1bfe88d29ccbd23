"""A hybrid state-space causal language model (``model_type``
``granitemoehybrid``; granite-4.0-h-micro is one): pre-norm blocks whose
mixer is, by ``layer_types``, a Mamba-2 layer (``mamba``: ops/ssm.py) or a
grouped-query attention layer without positions (``attention``:
ops/attention.py ``GroupedQueryAttention``), each followed by a gated SiLU
feed-forward of ``shared_intermediate_size`` (the family's routed experts
are not built: ``num_local_experts`` must be 0), RMSNorm throughout and
four published scalars:

    h <- embedding_multiplier * E[ids]
    h <- h + residual_multiplier * Mixer(RMSNorm(h))
    h <- h + residual_multiplier * FFN(RMSNorm(h))
    logits = RMSNorm(h) E^T / logits_scaling        (tied embeddings)

and ``attention_multiplier`` as the softmax scale.  The field names are
the public ``config.json``'s; ``layer_types`` holds the blocks built
here (a cut in depth is a shorter list, its length under ``num_layers``
beside the published ``num_hidden_layers``).

Every block is recomputed in the backward pass (``recompute_blocks``), as
``models/latent_moe.py`` does and for its reason: the boundaries are kept,
and with them the flash forward's named results; the scan's intermediates
(a chunk's decay matrix is 64 heads x 256 x 256 a chunk: in VMEM where
``ops/pallas/ssd_scan.py`` runs, in HBM elsewhere) are made again.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

from flexflow_tpu.config import FFConfig
from flexflow_tpu.machine import MachineModel
from flexflow_tpu.models.next_token import NextTokenLM, sgd_settings
from flexflow_tpu.strategy import Strategy

LAYER_TYPES = ("mamba", "attention")


@dataclasses.dataclass
class HybridSSMConfig:
    batch_size: int = 2
    seq_length: int = 64
    layer_types: Tuple[str, ...] = ("mamba", "attention")
    hidden_size: int = 64
    shared_intermediate_size: int = 128
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    attention_multiplier: float = 0.25
    mamba_n_heads: int = 4
    mamba_d_head: int = 32
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 16
    mamba_conv_bias: bool = True
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_norm_eps: float = 1e-5
    vocab_size: int = 256
    embedding_std: float = 0.05
    learning_rate: float = 1e-3
    num_iterations: int = 10
    compute_dtype: str = "float32"
    param_dtype: str = "float32"
    seed: int = 0
    #: further FFConfig fields by name (obs_dir, ckpt_dir, ...)
    ff: Dict = dataclasses.field(default_factory=dict)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_config(cls, config: Dict, **overrides) -> "HybridSSMConfig":
        """From a configuration file of the public ``config.json``'s keys
        (``benchmarks/configs/granite_4_0_h_micro.json`` is one); only
        the mechanisms this class builds are accepted."""
        want = {"hidden_act": "silu", "normalization_function": "rmsnorm",
                "position_embedding_type": "nope", "attention_bias": False,
                "mamba_proj_bias": False, "mamba_n_groups": 1,
                "num_local_experts": 0, "num_experts_per_tok": 0,
                "tie_word_embeddings": True}
        for key, value in want.items():
            if config.get(key, value) != value:
                raise ValueError(f"{key} = {config[key]!r}: this model "
                                 f"class builds {value!r} only")
        types = tuple(config["layer_types"])
        layers = int(config.get("num_layers",
                                config.get("num_hidden_layers", len(types))))
        if len(types) != layers:
            raise ValueError(f"{len(types)} layer_types for {layers} layers")
        inner = int(config["mamba_n_heads"]) * int(config["mamba_d_head"])
        if inner != int(config.get("mamba_expand", 2)) \
                * int(config["hidden_size"]):
            raise ValueError("mamba_n_heads x mamba_d_head is not "
                             "mamba_expand x hidden_size")
        own = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in own
              and k not in ("ff", "learning_rate", "layer_types")}
        kw["layer_types"] = types
        kw.update(sgd_settings(config))
        kw.update(overrides)
        return cls(**kw)


class HybridSSMLM(NextTokenLM):
    def __init__(self, t_config: HybridSSMConfig = None,
                 machine: Optional[MachineModel] = None,
                 strategies: Optional[Strategy] = None):
        self.t = t = t_config or HybridSSMConfig()
        unknown = set(t.layer_types) - set(LAYER_TYPES)
        if unknown:
            raise ValueError(f"layer_types {sorted(unknown)}: one of "
                             f"{LAYER_TYPES}")
        super().__init__(FFConfig(
            batch_size=t.batch_size, learning_rate=t.learning_rate,
            weight_decay=0.0, num_iterations=t.num_iterations,
            compute_dtype=t.compute_dtype, param_dtype=t.param_dtype,
            seed=t.seed, strategies=strategies or Strategy(), **t.ff),
            machine)
        self._build()

    def _build(self):
        from flexflow_tpu import obs

        t = self.t
        self.tokens = self.create_input((t.batch_size, t.seq_length),
                                        "int32", "tokens")
        self.labels = self.create_input((t.batch_size, t.seq_length),
                                        "int32", "labels")
        embedded = x = self.embed("embed", self.tokens, t.vocab_size,
                                  t.hidden_size, init_std=t.embedding_std,
                                  multiplier=t.embedding_multiplier)
        obs.count("ssm.layers", t.layer_types.count("mamba"), level=True)
        self.recompute_blocks = []
        for i, kind in enumerate(t.layer_types):
            first = len(self.layers)
            h = self.rms_norm(f"blk{i}_norm1", x, t.rms_norm_eps)
            if kind == "mamba":
                h = self.ssm_mixer(
                    f"blk{i}_ssm", h, t.mamba_n_heads, t.mamba_d_head,
                    t.mamba_d_state, t.mamba_d_conv, t.mamba_chunk_size,
                    t.mamba_conv_bias, t.rms_norm_eps)
            else:
                h = self.grouped_query_attention(
                    f"blk{i}_attn", h, t.num_attention_heads,
                    t.num_key_value_heads, t.head_dim,
                    t.attention_multiplier)
            x = self.add_seq(f"blk{i}_res1", x, h, t.residual_multiplier)
            h = self.rms_norm(f"blk{i}_norm2", x, t.rms_norm_eps)
            h = self.gated_ffn(f"blk{i}_ffn", h, t.shared_intermediate_size)
            x = self.add_seq(f"blk{i}_res2", x, h, t.residual_multiplier)
            self.recompute_blocks.append(range(first, len(self.layers)))
        x = self.rms_norm("final_norm", x, t.rms_norm_eps)
        logits = self.tied_head("lm_head", x, embedded, t.logits_scaling)
        self.softmax_seq("softmax", logits, self.labels)
        self.loss_op = self.layers[-1]
