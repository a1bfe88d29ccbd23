"""Model FLOPs and kernel work of ``smallthinker_21b_a3b`` (this chip's
share of it) from its shapes.  Nothing is counted for the blocks'
recomputation in the backward pass.  The keys a query meets and the
flash kernels' work are ``laguna_s_2_1``'s, at one head count for every
layer."""

from benchmarks.flops.laguna_s_2_1 import _flash, _pairs, keys_met  # noqa: F401


def _sizes(c):
    layers = int(c["num_layers"])
    return dict(
        layers=layers, d=int(c["hidden_size"]), hd=int(c["head_dim"]),
        heads=[int(c["num_attention_heads"])] * layers,
        kv=int(c["num_key_value_heads"]),
        windowed=[bool(v) for v in c["sliding_window_layout"][:layers]],
        window=int(c["sliding_window_size"]),
        eff=int(c["moe_ffn_hidden_size"]), router=int(c["router_outputs"]),
        held=int(c["experts_held"][1]) - int(c["experts_held"][0]),
        top_k=int(c["moe_num_active_primary_experts"]),
        vocab=int(c["vocab_size"]))


def held_experts_per_token(config) -> float:
    """Under a balanced router: top_k of router_outputs, of which held."""
    z = _sizes(config)
    return z["top_k"] * z["held"] / z["router"]


def train_flops_per_item(config, mix) -> float:
    """Forward + backward FLOPs of one token at the mix's sequence
    length: 2 x multiply-adds of each layer's four attention matrices, its
    scores and weighted values over the keys its queries meet
    (``keys_met``: all earlier ones on a full layer, at most the window's
    on a windowed one), the router, and the held experts a token meets
    under a balanced router; the vocabulary head over the rows held; all
    once forward and twice backward.  The embedding lookup is a gather,
    not a product, and rotary positions are elementwise."""
    z = _sizes(config)
    s = int(mix["seq_length"])
    d, hd, kv = z["d"], z["hd"], z["kv"]
    fwd = 2.0 * d * z["vocab"]
    for layer, h in enumerate(z["heads"]):
        fwd += 2.0 * d * (2 * h * hd + 2 * kv * hd)
        fwd += 4.0 * h * hd * _pairs(z, s, layer) / s
        fwd += 2.0 * d * z["router"] \
            + held_experts_per_token(config) * 6.0 * d * z["eff"]
    return 3.0 * fwd


def kernel_work(config, mix):
    """{kernel or operator: FLOPs and bytes a step needs from it}.

    ``ff_flash_``: flash attention forward and backward over all the
    layers, the NoPE full ones and the windowed ones, each at the keys its
    queries meet.  Keys repeated to one a query head, the pieces of a tile
    the mask leaves nothing of and the recomputed forward are not needed
    work.

    ``ff_flash_win_``: the same of the windowed layers alone (their
    kernels' names start so): the work is the window's, from shapes.

    ``grouped_mm``: the held experts' three products forward and six
    backward in each layer, at the balanced load of pairs; each product
    reads its rows and every held expert's matrix and writes its rows.
    The ReLU between them is elementwise and counts nothing."""
    z = _sizes(config)
    b, s = int(mix["batch"]), int(mix["seq_length"])
    itemsize = 2 if config["compute_dtype"] == "bfloat16" else 4
    every = range(z["layers"])
    pairs = b * s * held_experts_per_token(config)
    d, f = z["d"], z["eff"]
    return {"ff_flash_": _flash(z, b, s, every, itemsize),
            "ff_flash_win_": _flash(
                z, b, s, [l for l in every if z["windowed"][l]], itemsize),
            "grouped_mm": {
                "flops": z["layers"] * 9 * 2.0 * pairs * d * f,
                "bytes": z["layers"] * 9 * itemsize * (
                    pairs * d + z["held"] * d * f + pairs * f)}}
