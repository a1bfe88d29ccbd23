"""Model FLOPs and kernel work of ``granite_4_0_h_micro`` (the layers of
it this chip holds) from its shapes.  Nothing is counted for the blocks'
recomputation in the backward pass."""


def _sizes(c):
    h, hd = int(c["mamba_n_heads"]), int(c["mamba_d_head"])
    n = int(c["mamba_d_state"])
    return dict(
        mamba=list(c["layer_types"]).count("mamba"),
        attention=list(c["layer_types"]).count("attention"),
        d=int(c["hidden_size"]), ff=int(c["shared_intermediate_size"]),
        q=int(c["num_attention_heads"]), kv=int(c["num_key_value_heads"]),
        hd=int(c["hidden_size"]) // int(c["num_attention_heads"]),
        h=h, p=hd, n=n, inner=h * hd, conv=int(c["mamba_d_conv"]),
        proj=2 * h * hd + 2 * n + h, xbc=h * hd + 2 * n,
        chunk=int(c["mamba_chunk_size"]), vocab=int(c["vocab_size"]))


def scan_flops_per_token(config) -> float:
    """The chunked scan's four products a token at the published chunk:
    C B^T once for all heads (one group), and a head's (L * C B^T)
    (delta x) over the chunk, its own state and the entering state's
    part."""
    z = _sizes(config)
    return (2.0 * z["chunk"] * z["n"]
            + z["h"] * (2.0 * z["chunk"] * z["p"] + 4.0 * z["p"] * z["n"]))


def train_flops_per_item(config, mix) -> float:
    """Forward + backward FLOPs of one token at the mix's sequence
    length: 2 x multiply-adds of a Mamba layer's two projections, its
    depthwise convolution and its scan; of the attention layer's four
    projections and causal attention over 32 query heads (a query at
    position i meets i + 1 keys); of every layer's gated feed-forward; of
    the tied vocabulary head; all once forward and twice backward.  The
    embedding lookup is a gather, not a product."""
    z = _sizes(config)
    s = int(mix["seq_length"])
    d = z["d"]
    ffn = 6.0 * d * z["ff"]
    mamba = (2.0 * d * z["proj"] + 2.0 * z["conv"] * z["xbc"]
             + scan_flops_per_token(config) + 2.0 * z["inner"] * d)
    attn = (2.0 * d * z["hd"] * (2 * z["q"] + 2 * z["kv"])
            + 4.0 * z["q"] * z["hd"] * (s + 1) / 2)
    fwd = (z["mamba"] * (mamba + ffn) + z["attention"] * (attn + ffn)
           + 2.0 * d * z["vocab"])
    return 3.0 * fwd


def kernel_work(config, mix):
    """{kernel or operator: FLOPs and bytes a step needs from it}.

    ``ff_flash_``: causal flash attention forward and backward in each
    attention layer: six S x S products a query head, half of each under
    the causal mask; q, the result and their gradients are moved at the
    32 query heads (forward reads q and writes o; backward reads q, o, do
    and writes dq), k, v and their gradients at the 8 key-value heads
    (read forward, read backward, dk and dv written).  Keys repeated to
    one a query head and the recomputed forward are not needed work.

    ``ssd_scan``: the state-space scan of every Mamba layer forward and
    twice backward, at the published chunk; the forward reads x, B, C
    (compute type) and delta (float32, one a head) and writes y, the
    backward reads them and dy and writes their four gradients.  The
    FLOPs bound it (9.6 ms a step against 7.8 by the bytes at 2 x 8192
    tokens)."""
    z = _sizes(config)
    b, s = int(mix["batch"]), int(mix["seq_length"])
    itemsize = 2 if config["compute_dtype"] == "bfloat16" else 4
    hd = z["hd"]
    flash_flops = z["attention"] * b * z["q"] * 2.0 * s * s * 6 * hd / 2
    flash_bytes = z["attention"] * b * s * hd * itemsize * (
        6 * z["q"] + 6 * z["kv"])
    operands = (z["inner"] + 2 * z["n"]) * itemsize + 4 * z["h"]
    y = z["inner"] * itemsize
    # forward: operands in, y out; backward: operands and dy in, the
    # operands' gradients out
    scan_bytes = z["mamba"] * b * s * (
        (operands + y) + (operands + y) + operands)
    scan_flops = z["mamba"] * b * s * 3.0 * scan_flops_per_token(config)
    return {"ff_flash_": {"flops": flash_flops, "bytes": flash_bytes},
            "ssd_scan": {"flops": scan_flops, "bytes": scan_bytes}}
