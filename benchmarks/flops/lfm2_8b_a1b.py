"""Model FLOPs and kernel work of ``lfm2_8b_a1b`` (this chip's share of
it) from its shapes.  Nothing is counted for the blocks' recomputation in
the backward pass."""


def _sizes(c):
    layers = int(c["num_layers"])
    heads = int(c["num_attention_heads"])
    return dict(
        layers=layers, d=int(c["hidden_size"]), heads=heads,
        hd=int(c["hidden_size"]) // heads,
        kv=int(c["num_key_value_heads"]),
        conv=[k == "conv" for k in c["layer_types"][:layers]],
        dense=min(int(c["num_dense_layers"]), layers),
        ff=int(c["intermediate_size"]),
        eff=int(c["moe_intermediate_size"]),
        router=int(c["router_outputs"]),
        held=int(c["experts_held"][1]) - int(c["experts_held"][0]),
        top_k=int(c["num_experts_per_tok"]), vocab=int(c["vocab_size"]))


def held_experts_per_token(config) -> float:
    """Under a balanced router: top_k of router_outputs, of which held."""
    z = _sizes(config)
    return z["top_k"] * z["held"] / z["router"]


def keys_met(s: int) -> int:
    """(query, key) pairs of one head over a sequence of s positions: a
    query at position i meets i + 1 keys."""
    return s * (s + 1) // 2


def train_flops_per_item(config, mix) -> float:
    """Forward + backward FLOPs of one token at the mix's sequence
    length: 2 x multiply-adds of a conv layer's two matrices (hidden -> 3
    x hidden and hidden -> hidden), of an attention layer's four matrices
    and its scores and weighted values over the keys its queries meet, of
    a dense layer's gated feed-forward, and in each expert layer of the
    router and the held experts a token meets under a balanced router;
    the vocabulary head over the rows held; all once forward and twice
    backward.  The embedding lookup is a gather, the convolution's taps,
    the gates and the norms elementwise: not products, not counted."""
    z = _sizes(config)
    s = int(mix["seq_length"])
    d, hd = z["d"], z["hd"]
    fwd = 2.0 * d * z["vocab"]
    for layer, conv in enumerate(z["conv"]):
        if conv:
            fwd += 2.0 * d * 4 * d
        else:
            fwd += 2.0 * d * (2 * z["heads"] * hd + 2 * z["kv"] * hd)
            fwd += 4.0 * z["heads"] * hd * keys_met(s) / s
        if layer < z["dense"]:
            fwd += 6.0 * d * z["ff"]
        else:
            fwd += (2.0 * d * z["router"]
                    + held_experts_per_token(config) * 6.0 * d * z["eff"])
    return 3.0 * fwd


def kernel_work(config, mix):
    """{kernel or operator: FLOPs and bytes a step needs from it}.

    ``ff_flash_``: flash attention forward and backward over the
    attention layers: six products a (query, key) pair and head width
    (scores and values forward; dV, dP, dQ, dK backward); q, the result
    and their gradients moved at the query heads, k, v and their
    gradients at the key-value heads.  Keys repeated to one a query
    head, the pieces of a tile the mask leaves nothing of and the
    recomputed forward are not needed work.

    ``grouped_mm``: the held experts' three products forward and six
    backward in each expert layer, at the balanced load of pairs; each
    product reads its rows and every held expert's matrix and writes its
    rows.

    ``short_conv``: the conv operators' two products forward and four
    backward (each product's gradient by its input and by its matrix),
    and the bytes of x, ``[B | C | X]``, ``C * v`` and the result, each
    moved once a pass (forward, backward) in the compute type.  The
    taps and the two gates are elementwise on arrays already counted."""
    z = _sizes(config)
    b, s = int(mix["batch"]), int(mix["seq_length"])
    itemsize = 2 if config["compute_dtype"] == "bfloat16" else 4
    tokens = b * s
    d, f, hd = z["d"], z["eff"], z["hd"]
    convs = z["conv"].count(True)
    attns = z["layers"] - convs
    moe_layers = z["layers"] - z["dense"]
    pairs = tokens * held_experts_per_token(config)
    return {"ff_flash_": {
                "flops": attns * b * z["heads"] * 12.0 * hd * keys_met(s),
                "bytes": attns * tokens * hd * itemsize * (
                    6 * z["heads"] + 6 * z["kv"])},
            "grouped_mm": {
                "flops": moe_layers * 9 * 2.0 * pairs * d * f,
                "bytes": moe_layers * 9 * itemsize * (
                    pairs * d + z["held"] * d * f + pairs * f)},
            "short_conv": {
                "flops": convs * 3 * 2.0 * tokens * d * 4 * d,
                "bytes": convs * 2 * itemsize * tokens * 6 * d}}
