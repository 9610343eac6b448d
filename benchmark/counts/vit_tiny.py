"""DeiT-Ti-width vision transformer on patch tokens: the patch
embedding, ``depth`` blocks (four projections, the two attention
products, the two MLP layers) and the head; every layer trained."""

from flops import train


def per_sample(config, scenario):
    arch = config["architecture"]
    h, w, cin = arch["input"]
    p, d, t = arch["patch"], arch["embed_dim"], arch["tokens"]
    heads, hd, f = arch["num_heads"], arch["head_dim"], arch["mlp_dim"]
    patch = 2 * t * p * p * cin * d
    qkvo = 4 * 2 * t * d * heads * hd
    attn = 2 * 2 * heads * t * t * hd
    mlp = 2 * 2 * t * d * f
    head = 2 * d * arch["num_classes"]
    fwd = [patch] + [qkvo + attn + mlp] * arch["depth"] + [head]
    return {"forward": sum(fwd), "train": train(fwd)}
