"""Operations the forward and backward passes of one training sample
REQUIRE, by configuration: multiply-adds of the GEMMs and convolutions
counted as 2 each, from the configuration's shapes alone. The same
whatever implements the step: no recomputation (remat), no layout
copies, no optimizer or loss arithmetic, and no tap of a 'same'
convolution that falls on the zero padding (an im2col GEMM multiplies
those zeros; the convolution does not need them). Backward counts the
weight gradient and the input gradient of every layer but the first,
whose input needs none.
"""


def _same_taps(n, k):
    """Kernel taps that land inside an n-wide axis, summed over the n
    output positions of a stride-1 'same' convolution."""
    r = k // 2
    return n * k - r * (r + 1)


def _train(layers):
    """layers: forward FLOPs per sample, first layer first."""
    return 3 * sum(layers) - layers[0]


def femnist_cnn(arch):
    h, w, cin = arch["input"]
    k = arch["conv_kernel"]
    c1, c2 = arch["conv_channels"]
    hid, ncls = arch["hidden_dim"], arch["num_classes"]
    conv1 = 2 * _same_taps(h, k) * _same_taps(w, k) * cin * c1
    conv2 = 2 * _same_taps(h // 2, k) * _same_taps(w // 2, k) * c1 * c2
    fc1 = 2 * (h // 4) * (w // 4) * c2 * hid
    fc2 = 2 * hid * ncls
    fwd = [conv1, conv2, fc1, fc2]
    return {"forward": sum(fwd), "train": _train(fwd)}


def vit_tiny(arch):
    h, w, cin = arch["input"]
    p, d, t = arch["patch"], arch["embed_dim"], arch["tokens"]
    heads, hd, f = arch["num_heads"], arch["head_dim"], arch["mlp_dim"]
    patch = 2 * t * p * p * cin * d
    qkvo = 4 * 2 * t * d * heads * hd
    attn = 2 * 2 * heads * t * t * hd
    mlp = 2 * 2 * t * d * f
    head = 2 * d * arch["num_classes"]
    fwd = [patch] + [qkvo + attn + mlp] * arch["depth"] + [head]
    return {"forward": sum(fwd), "train": _train(fwd)}


def round_flops(cell_config, scenario, rows_per_node):
    """Required training FLOPs of one federated round of a cell."""
    per = globals()[cell_config["flops"]](cell_config["architecture"])
    batch = scenario["data"]["batch_size"]
    steps = max(rows_per_node // batch, 1) * scenario["training"]["epochs_per_round"]
    return scenario["n_nodes"] * steps * min(batch, rows_per_node) * per["train"]
