"""Model FLOPs and kernel work of ``ling_3_0_flash`` (this chip's share of
it) from its shapes.  Nothing is counted for the blocks' recomputation in
the backward pass.

The KDA scan is counted as its chunked form computes it (``ops/kda.py``),
a head of key and value width ``K`` over chunks of ``l`` positions, the
products a chunk:

    A (k with k) and M (q with k), in sub-chunk rows   2 x 2 l^2 K
    the triangular solve for W and U0                  l^2 (K + V)
    U0 - W S and the entering state's part of O        2 x 2 l K V
    M U                                                2 l^2 V
    P = Diag - K^T W and R = K^T U0                    2 l K^2 + 2 l K V
    the walk S' = P S + R                              2 K^2 V

so at K = V a token costs ``8 l K + 8 K^2 + 2 K^3 / l`` a head: at the
published chunk of 64 and heads of 128, 65 536 + 131 072 + 65 536 =
262 144 FLOP, 8.39 M a layer of 32 heads; a chunk of 128 would read
131 072 + 131 072 + 32 768 = 294 912, one of 32 at 32 768 + 131 072 +
131 072 = 294 912.  A sequence shorter than the chunk is one chunk of
whole sub-chunks of 16."""


def _sizes(c):
    layers = int(c["num_layers"])
    return dict(
        layers=layers, dense=int(c["first_k_dense_replace"]),
        mla=[(i + 1) % int(c["layer_group_size"]) == 0
             for i in range(layers)],
        d=int(c["hidden_size"]), h=int(c["num_attention_heads"]),
        hd=int(c["head_dim"]), taps=int(c["short_conv_kernel_size"]),
        qk=int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"]),
        nope=int(c["qk_nope_head_dim"]), rope=int(c["qk_rope_head_dim"]),
        v=int(c["v_head_dim"]), rank=int(c["kv_lora_rank"]),
        ff=int(c["intermediate_size"]), eff=int(c["moe_intermediate_size"]),
        shared=int(c["num_shared_experts"])
        * int(c["moe_shared_expert_intermediate_size"]),
        router=int(c["router_outputs"]),
        held=int(c["experts_held"][1]) - int(c["experts_held"][0]),
        top_k=int(c["num_experts_per_tok"]), vocab=int(c["vocab_size"]))


def held_experts_per_token(config) -> float:
    """Under a balanced router: top_k of router_outputs, of which held."""
    z = _sizes(config)
    return z["top_k"] * z["held"] / z["router"]


def kda_chunk(s: int) -> int:
    """The chunk a sequence of s positions is walked in (ops/kda.py)."""
    return min(64, -(-s // 16) * 16)


def kda_scan_flops_per_token(config, s: int) -> float:
    """One KDA layer's chunked scan, forward, a token (the docstring's
    arithmetic)."""
    z = _sizes(config)
    l, k = kda_chunk(s), z["hd"]
    return z["h"] * (8.0 * l * k + 8.0 * k * k + 2.0 * k ** 3 / l)


def train_flops_per_item(config, mix) -> float:
    """Forward + backward FLOPs of one token at the mix's sequence
    length: 2 x multiply-adds of each KDA layer's product in (q, k, v, the
    decay, the output gate, beta) and out and its chunked scan, of each
    latent-attention layer's five matrices (q, the compressed key-value
    and its expansion, the head gate, o) and its causal attention at
    query/key width nope + rope and value width v (a query at position i
    meets i + 1 keys), the dense blocks' gated feed-forward, and in each
    expert block the router, the shared expert and the held experts a
    token meets under a balanced router; the vocabulary head over the rows
    held; all once forward and twice backward.  The embedding lookup is a
    gather, the convolutions, norms and gates elementwise: none counts."""
    z = _sizes(config)
    s = int(mix["seq_length"])
    d, h, di = z["d"], z["h"], z["h"] * z["hd"]
    kda = 2.0 * d * (5 * di + h) + 2.0 * di * d \
        + kda_scan_flops_per_token(config, s)
    mla = 2.0 * (d * h * z["qk"] + d * (z["rank"] + z["rope"])
                 + z["rank"] * h * (z["nope"] + z["v"]) + d * h
                 + h * z["v"] * d) \
        + 2.0 * h * (z["qk"] + z["v"]) * (s + 1) / 2
    dense = 6.0 * d * z["ff"]
    expert = (2.0 * d * z["router"] + 6.0 * d * z["shared"]
              + held_experts_per_token(config) * 6.0 * d * z["eff"])
    fwd = 2.0 * d * z["vocab"]
    for layer in range(z["layers"]):
        fwd += mla if z["mla"][layer] else kda
        fwd += dense if layer < z["dense"] else expert
    return 3.0 * fwd


def kernel_work(config, mix):
    """{kernel or operator: FLOPs and bytes a step needs from it}.

    ``ff_flash_``: causal flash attention forward and backward over the
    latent-attention layers, as ``moonlight_16b_a3b``'s: six S x S
    products a head, half of each under the mask, q, k, v (and o, do)
    read and o, dq, dk, dv written at their true widths in the compute
    type.

    ``grouped_mm``: the held experts' three products forward and six
    backward in each expert layer, at the balanced load of pairs; each
    product reads its rows and every held expert's matrix and writes its
    rows.

    ``kda_scan``: the KDA layers' chunked scan, once forward and twice
    backward (the docstring's FLOPs), and the least it moves: forward q,
    k, v read and o written in the compute type, the decays read in
    float32 and beta; backward q, k, v, do read and dq, dk, dv written in
    the compute type, the decays read and their gradient written in
    float32, beta read and its gradient written.  The states between
    chunks, the factors of the sub-chunks and the recomputed forward are
    not needed work."""
    z = _sizes(config)
    b, s = int(mix["batch"]), int(mix["seq_length"])
    itemsize = 2 if config["compute_dtype"] == "bfloat16" else 4
    qk, v = z["qk"], z["v"]
    mla_layers = sum(z["mla"])
    flash_flops = mla_layers * b * z["h"] * 2.0 * s * s * (
        3 * qk + 3 * v) / 2
    flash_bytes = mla_layers * b * z["h"] * s * itemsize * (
        (2 * qk + 2 * v) + (4 * qk + 4 * v))
    pairs = b * s * held_experts_per_token(config)
    moe_layers = z["layers"] - min(z["dense"], z["layers"])
    d, f = z["d"], z["eff"]
    kda_layers = z["layers"] - mla_layers
    di, h = z["h"] * z["hd"], z["h"]
    return {"ff_flash_": {"flops": flash_flops, "bytes": flash_bytes},
            "grouped_mm": {
                "flops": moe_layers * 9 * 2.0 * pairs * d * f,
                "bytes": moe_layers * 9 * itemsize * (
                    pairs * d + z["held"] * d * f + pairs * f)},
            "kda_scan": {
                "flops": kda_layers * 3.0 * b * s
                * kda_scan_flops_per_token(config, s),
                "bytes": kda_layers * b * s * (
                    (4 + 7) * di * itemsize + (1 + 2) * 4 * di
                    + (1 + 2) * 4 * h)}}
