"""LEAF FEMNIST CNN: two 5x5 'same' convolutions, each followed by a
2x2 pool, and two dense layers; every layer trained."""

from flops import same_taps, train


def per_sample(config, scenario):
    arch = config["architecture"]
    h, w, cin = arch["input"]
    k = arch["conv_kernel"]
    c1, c2 = arch["conv_channels"]
    hid, ncls = arch["hidden_dim"], arch["num_classes"]
    conv1 = 2 * same_taps(h, k) * same_taps(w, k) * cin * c1
    conv2 = 2 * same_taps(h // 2, k) * same_taps(w // 2, k) * c1 * c2
    fc1 = 2 * (h // 4) * (w // 4) * c2 * hid
    fc2 = 2 * hid * ncls
    fwd = [conv1, conv2, fc1, fc2]
    return {"forward": sum(fwd), "train": train(fwd)}
