"""Model FLOPs and kernel work of ``laguna_s_2_1`` (this chip's share of
it) from its shapes.  Nothing is counted for the blocks' recomputation in
the backward pass."""


def _sizes(c):
    layers = int(c["num_layers"])
    return dict(
        layers=layers, d=int(c["hidden_size"]), hd=int(c["head_dim"]),
        kv=int(c["num_key_value_heads"]),
        heads=[int(h) for h in c["num_attention_heads_per_layer"][:layers]],
        windowed=[k == "sliding_attention"
                  for k in c["layer_types"][:layers]],
        dense=[k == "dense" for k in c["mlp_layer_types"][:layers]],
        window=int(c["sliding_window"]), ff=int(c["intermediate_size"]),
        eff=int(c["moe_intermediate_size"]),
        shared=int(c["shared_expert_intermediate_size"]),
        router=int(c["router_outputs"]),
        held=int(c["experts_held"][1]) - int(c["experts_held"][0]),
        top_k=int(c["num_experts_per_tok"]), vocab=int(c["vocab_size"]))


def held_experts_per_token(config) -> float:
    """Under a balanced router: top_k of router_outputs, of which held."""
    z = _sizes(config)
    return z["top_k"] * z["held"] / z["router"]


def keys_met(s: int, window=None) -> int:
    """(query, key) pairs of one head over a sequence of s positions: a
    query at position i meets i + 1 keys, under a window at most
    ``window`` of them."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def _pairs(z, s, layer):
    return keys_met(s, z["window"] if z["windowed"][layer] else None)


def train_flops_per_item(config, mix) -> float:
    """Forward + backward FLOPs of one token at the mix's sequence
    length: 2 x multiply-adds of each layer's five attention matrices
    (q, k, v, the per-head gate, o) at its own head count, its scores and
    weighted values over the keys its queries meet (``keys_met``: all
    earlier ones on a full layer, at most the window's on a sliding one),
    the dense layer's gated feed-forward, and in each expert layer the
    router, the shared expert and the held experts a token meets under a
    balanced router; the vocabulary head over the rows held; all once
    forward and twice backward.  The embedding lookup is a gather, not a
    product."""
    z = _sizes(config)
    s = int(mix["seq_length"])
    d, hd = z["d"], z["hd"]
    fwd = 2.0 * d * z["vocab"]
    for layer, h in enumerate(z["heads"]):
        fwd += 2.0 * d * (2 * h * hd + 2 * z["kv"] * hd + h)
        fwd += 4.0 * h * hd * _pairs(z, s, layer) / s
        if z["dense"][layer]:
            fwd += 6.0 * d * z["ff"]
        else:
            fwd += (2.0 * d * z["router"] + 6.0 * d * z["shared"]
                    + held_experts_per_token(config) * 6.0 * d * z["eff"])
    return 3.0 * fwd


def _flash(z, b, s, layers, itemsize):
    """Six products a (query, key) pair and head width (scores and values
    forward; dV, dP, dQ, dK backward); q, the result and their gradients
    moved at the layer's query heads (forward reads q and writes o,
    backward reads q, o, do and writes dq), k, v and their gradients at
    the key-value heads (read forward, read backward, dk and dv
    written)."""
    flops = sum(b * z["heads"][l] * 12.0 * z["hd"] * _pairs(z, s, l)
                for l in layers)
    moved = sum(b * s * z["hd"] * itemsize * (6 * z["heads"][l]
                                              + 6 * z["kv"])
                for l in layers)
    return {"flops": flops, "bytes": moved}


def kernel_work(config, mix):
    """{kernel or operator: FLOPs and bytes a step needs from it}.

    ``ff_flash_``: flash attention forward and backward over all the
    layers, each at its own head count and the keys its queries meet.
    Keys repeated to one a query head, the pieces of a tile the mask
    leaves nothing of and the recomputed forward are not needed work.

    ``ff_flash_win_``: the same of the sliding layers alone (their
    kernels' names start so): the work is the window's, from shapes, so
    whatever computes the window is held to it.

    ``grouped_mm``: the held experts' three products forward and six
    backward in each expert layer, at the balanced load of pairs; each
    product reads its rows and every held expert's matrix and writes its
    rows."""
    z = _sizes(config)
    b, s = int(mix["batch"]), int(mix["seq_length"])
    itemsize = 2 if config["compute_dtype"] == "bfloat16" else 4
    every = range(z["layers"])
    pairs = b * s * held_experts_per_token(config)
    moe_layers = z["dense"].count(False)
    d, f = z["d"], z["eff"]
    return {"ff_flash_": _flash(z, b, s, every, itemsize),
            "ff_flash_win_": _flash(
                z, b, s, [l for l in every if z["windowed"][l]], itemsize),
            "grouped_mm": {
                "flops": moe_layers * 9 * 2.0 * pairs * d * f,
                "bytes": moe_layers * 9 * itemsize * (
                    pairs * d + z["held"] * d * f + pairs * f)}}
