"""Model FLOPs and kernel work of ``gpt2_small`` from its shapes."""


def _sizes(config):
    d = int(config["n_embd"])
    return (int(config["n_layer"]), d, int(config["n_head"]),
            int(config.get("n_inner") or 4 * d), int(config["vocab_size"]))


def train_flops_per_item(config, mix) -> float:
    """Forward + backward FLOPs of one token at the mix's sequence
    length: 2 x multiply-adds of every projection (q, k, v, o, the two
    feed-forward matrices, the vocabulary head), plus causal attention
    (a query at position i meets i + 1 keys: 2 d (S + 1) a token for
    scores and values together), all once forward and twice backward.
    The embedding lookups are gathers, not products."""
    layers, d, _, d_ff, vocab = _sizes(config)
    s = int(mix["seq_length"])
    per_layer = 2.0 * (4 * d * d + 2 * d * d_ff) + 2.0 * d * (s + 1)
    return 3.0 * (layers * per_layer + 2.0 * d * vocab)


def kernel_work(config, mix):
    """{kernel name prefix: FLOPs and bytes a step needs from it}.  Flash
    attention, causal, forward and backward over every layer: six
    S x S x head_dim products a head (scores and values forward; dV, dP,
    dQ, dK backward; the backward's recomputation of the scores is not
    needed work), half of each under the causal mask; it must read q, k,
    v forward and q, k, v, o, do backward and write o, dq, dk, dv, each
    batch x heads x S x head_dim in the compute type."""
    layers, d, heads, _, _ = _sizes(config)
    b, s = int(mix["batch"]), int(mix["seq_length"])
    hd = d // heads
    itemsize = 2 if config["compute_dtype"] == "bfloat16" else 4
    flops = layers * b * heads * 6 * 2.0 * s * s * hd / 2
    bytes_ = layers * 12.0 * b * heads * s * hd * itemsize
    return {"ff_flash_": {"flops": flops, "bytes": bytes_}}
