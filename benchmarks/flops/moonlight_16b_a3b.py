"""Model FLOPs and kernel work of ``moonlight_16b_a3b`` (this chip's
share of it) from its shapes.  Nothing is counted for the blocks'
recomputation in the backward pass."""


def _sizes(c):
    return dict(
        layers=int(c["num_layers"]), dense=int(c["first_k_dense_replace"]),
        d=int(c["hidden_size"]), h=int(c["num_attention_heads"]),
        qk=int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"]),
        nope=int(c["qk_nope_head_dim"]), rope=int(c["qk_rope_head_dim"]),
        v=int(c["v_head_dim"]), rank=int(c["kv_lora_rank"]),
        ff=int(c["intermediate_size"]), eff=int(c["moe_intermediate_size"]),
        shared=int(c["n_shared_experts"]), router=int(c["router_outputs"]),
        held=int(c["experts_held"][1]) - int(c["experts_held"][0]),
        top_k=int(c["num_experts_per_tok"]), vocab=int(c["vocab_size"]))


def held_experts_per_token(config) -> float:
    """Under a balanced router: top_k of router_outputs, of which held."""
    z = _sizes(config)
    return z["top_k"] * z["held"] / z["router"]


def train_flops_per_item(config, mix) -> float:
    """Forward + backward FLOPs of one token at the mix's sequence
    length: 2 x multiply-adds of the latent attention's four projections,
    causal attention at query/key width nope + rope and value width v (a
    query at position i meets i + 1 keys), the dense block's gated
    feed-forward, and in each expert block the router, the shared experts
    and the held experts a token meets under a balanced router; the
    vocabulary head over the rows held; all once forward and twice
    backward.  The embedding lookup is a gather, not a product."""
    z = _sizes(config)
    s = int(mix["seq_length"])
    d, h = z["d"], z["h"]
    proj = 2.0 * (d * h * z["qk"] + d * (z["rank"] + z["rope"])
                  + z["rank"] * h * (z["nope"] + z["v"]) + h * z["v"] * d)
    attn = 2.0 * h * (z["qk"] + z["v"]) * (s + 1) / 2
    dense = 6.0 * d * z["ff"]
    expert = (2.0 * d * z["router"] + 6.0 * d * z["shared"] * z["eff"]
              + held_experts_per_token(config) * 6.0 * d * z["eff"])
    fwd = (z["layers"] * (proj + attn) + z["dense"] * dense
           + (z["layers"] - z["dense"]) * expert + 2.0 * d * z["vocab"])
    return 3.0 * fwd


def kernel_work(config, mix):
    """{kernel or operator: FLOPs and bytes a step needs from it}.

    ``ff_flash_``: causal flash attention forward and backward over every
    layer: six S x S products a head (scores at the query/key width and
    values at the value width forward; dV and dP at the value width, dQ
    and dK at the query/key width backward), half of each under the causal
    mask; it reads q, k, v forward and q, k, v, o, do backward and writes
    o, dq, dk, dv, each at its own true width in the compute type.  The
    padding of q and k to whole lanes and the recomputed forward are not
    needed work.

    ``grouped_mm``: the held experts' three products forward and six
    backward in each expert layer, at the balanced load of pairs; each
    product reads its rows and every held expert's matrix and writes its
    rows."""
    z = _sizes(config)
    b, s = int(mix["batch"]), int(mix["seq_length"])
    itemsize = 2 if config["compute_dtype"] == "bfloat16" else 4
    qk, v = z["qk"], z["v"]
    flash_flops = z["layers"] * b * z["h"] * 2.0 * s * s * (
        3 * qk + 3 * v) / 2
    flash_bytes = z["layers"] * b * z["h"] * s * itemsize * (
        (2 * qk + 2 * v) + (4 * qk + 4 * v))
    pairs = b * s * held_experts_per_token(config)
    moe_layers = z["layers"] - z["dense"]
    d, f = z["d"], z["eff"]
    gmm_flops = moe_layers * 9 * 2.0 * pairs * d * f
    gmm_bytes = moe_layers * 9 * itemsize * (pairs * d + z["held"] * d * f
                                             + pairs * f)
    return {"ff_flash_": {"flops": flash_flops, "bytes": flash_bytes},
            "grouped_mm": {"flops": gmm_flops, "bytes": gmm_bytes}}
