"""Plain reference of Kimi-Linear-48B-A3B (``kimi_linear``) as a frozen
base under rank-r adapters: float32, ``highest`` matmul precision,
straight ``jax.numpy``; the KDA state as the recurrence it is defined by
(a ``lax.scan`` over positions, exact for any decay), latent attention a
block of queries at a time against all keys, the experts as a loop over
the ones held. It imports nothing of the program and is written from
the model's public ``config.json`` (sizes, ``linear_attn_config``,
``mla_use_nope``, the router's keys), the Kimi Linear description of
Kimi Delta Attention and DeepSeek-V3's latent attention and router.

Layers. ``x += Mixer(RMSNorm(x)); x += FFN(RMSNorm(x))``, RMSNorm ``w x
/ sqrt(mean(x^2) + 1e-5)``, no biases but ``b_g``, one RMSNorm after the
last layer, an untied head. Published layer ``l`` (1-indexed) has a KDA
mixer where ``l`` is in ``kda_layers`` and an MLA mixer where it is in
``full_attn_layers``; a dense SwiGLU FFN where ``l <=
first_k_dense_replace``, else the expert FFN.

- KDA (32 heads of 128): ``q, k, v = SiLU(conv4(x W_q))`` and so on,
  each convolution depthwise and causal over the 4096 channels, 4 taps,
  no bias; q and k L2-normalised a head, q scaled by ``128^-0.5``; ``g =
  -exp(A_log_h) softplus(x W_fa W_fb + dt_bias)`` [T, 32, 128], no
  floor; ``beta = sigmoid(x W_b)`` [T, 32]; ``S_t = (I - b_t k_t k_t^T)
  Diag(exp g_t) S_(t-1) + b_t k_t v_t^T``, ``o_t = S_t^T q_t``; output
  ``(RMSNorm_128(o) * sigmoid(x W_ga W_gb + b_g)) W_o``, one norm weight
  shared by the heads, one gate a channel.
- MLA: ``q = x W_q`` [T, 32, 192] (no compression); ``[c | k_R] = x
  W_dkv``; ``c <- RMSNorm_512(c)``; ``[k_N | v] = c W_ukv`` [T, 32, 128
  + 128]; ``k = [k_N | k_R]``, ``k_R`` every head's; no rotary embedding
  (``mla_use_nope``), no q/k norm, no output gate; scores ``q k^T /
  sqrt(192)``, causal, softmax; ``concat(o) W_o``.
- Dense FFN (layer 1): SwiGLU of 9216.
- Expert FFN: ``s = sigmoid(x W_r)`` over all 256; the 8 largest of ``s
  + b`` chosen (``b`` for the choice only; one group, always kept);
  weights ``2.446 s_i / sum_chosen s_j``; each expert a SwiGLU of 1024,
  plus one shared SwiGLU expert of 1024, unweighted.

Assumed (the configuration file lists the same under ``assumed``):
``mla_use_nope`` read as no rotary embedding on any dimension; ``A_log
= log U(1, 16)`` a head and ``dt_bias = 0`` (flash-linear-attention's
KDA layer initialisation); the output norm's weight one vector of 128;
the selection bias a frozen seeded vector.

Departures of this file from that description:

- the expert layer computes the part of the experts HELD HERE
  (``experts_held`` from ``expert_offset``, a deployment that spreads
  each layer over chips): it routes over all experts and leaves out what
  the absent ones would add; ``held_experts`` gathers, for each held
  expert, the rows that chose it into the smallest of a ladder of static
  sizes that holds them (its even share of the rows times a power of
  two, every row the last): the same sum whatever the size;
- ``kda_recurrence`` nests the scan (blocks of 64 positions under
  ``jax.checkpoint``) so that its backward pass keeps T/64 states and
  not T: the same recurrence, recomputed; every layer is recomputed on
  the way back, the recurrence with it, and a KDA layer's projections,
  convolutions and gates once more inside that (the epoch then holds
  15.3 GiB of the chip's 15.75 by its compile's memory analysis, the
  base among it); a position's two products are one read of the state
  before the step, ``k^T Diag(a) S = (a k)^T S`` and ``S_t^T q = (a
  q)^T S + (q . k) u`` (time: the same sums, the state read once);
- attention is computed ``QUERY_BLOCK`` queries at a time (memory), each
  block against the keys up to the end of its group of blocks with the
  mask written out (time: 10 of 16 parts of the square at 4 groups;
  at 8, 36 of 64, the epoch held 2.4 GB more).

The harness tells a reference module nothing of the run, so the sizes
are read here from the configuration's own file: its
``scenario.model.kwargs`` where a TPU is attached and its
``rehearse.scenario.model.kwargs`` elsewhere. Tests call
:func:`configure` with sizes of their own.
"""

import functools
import json
import math
import pathlib

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
#: queries of one attention block
QUERY_BLOCK = 512
#: groups of query blocks, each against the keys up to its own end
KEY_GROUPS = 4
#: positions of the recurrence a step of its loop
UNROLL = 4

SIZES: dict = {}
SHAPES: dict = {}
FROZEN_SHAPES: dict = {}


def configure(kwargs, lora):
    """Set the module's sizes from the model's keyword arguments (the
    program's ``model.kwargs``) and the scenario's ``lora`` keys."""
    z = dict(kwargs)
    z["rank"], z["alpha"] = lora["rank"], lora.get("alpha") or lora["rank"]
    d, H, K = z["hidden"], z["heads"], z["head_dim"]
    N, R, Dv, C = z["nope"], z["rope"], z["v_dim"], z["kv_rank"]
    z["kinds"] = [(l, "kda" if l in z["kda_layers"] else "mla",
                   l > z["first_dense"]) for l in range(1, z["layers"] + 1)]
    frozen = {"embed": (z["vocab"], d), "head": (d, z["vocab"]),
              "final_norm": (d,)}
    sites = {}
    for l, mixer, experts in z["kinds"]:
        L = f"L{l}."
        frozen[L + "mixer_norm"] = frozen[L + "ffn_norm"] = (d,)
        if mixer == "kda":
            for n in "qkv":
                sites[L + "kda_" + n] = (d, H * K)
                frozen[L + n + "_conv"] = (z["conv"], H * K)
            sites[L + "kda_o"] = (H * K, d)
            frozen.update({
                L + "kda_fa": (d, K), L + "kda_fb": (K, H * K),
                L + "A_log": (H,), L + "dt_bias": (H * K,),
                L + "kda_b": (d, H), L + "kda_ga": (d, K),
                L + "kda_gb": (K, H * K), L + "kda_gb.bias": (H * K,),
                L + "o_norm": (K,)})
        else:
            sites.update({L + "mla_q": (d, H * (N + R)),
                          L + "mla_dkv": (d, C + R),
                          L + "mla_ukv": (C, H * (N + Dv)),
                          L + "mla_o": (H * Dv, d)})
            frozen[L + "c_norm"] = (C,)
        if experts:
            E, W = z["experts_held"], z["expert_width"]
            frozen.update({
                L + "router": (d, z["n_experts"]),
                L + "router_bias": (z["n_experts"],),
                L + "experts_gate_up": (E, d, 2 * W),
                L + "experts_down": (E, W, d),
                L + "shared_gate_up": (d, 2 * z["shared_width"]),
                L + "shared_down": (z["shared_width"], d)})
        else:
            frozen.update({L + "ffn_gate_up": (d, 2 * z["dense_width"]),
                           L + "ffn_down": (z["dense_width"], d)})
    frozen.update(sites)
    trained = {}
    for name, (d_in, d_out) in sites.items():
        trained[name + ".A"] = (d_in, z["rank"])
        trained[name + ".B"] = (z["rank"], d_out)
    SIZES.clear(), SHAPES.clear(), FROZEN_SHAPES.clear()
    SIZES.update(z), SHAPES.update(trained), FROZEN_SHAPES.update(frozen)


def _configure_from_file():
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1]
                      / "configs" / "kimi-linear-48b-a3b.json").read_text())
    scen = cfg["scenario"]
    kwargs = dict(scen["model"]["kwargs"])
    if jax.default_backend() != "tpu":
        kwargs.update(cfg["rehearse"]["scenario"]["model"]["kwargs"])
    configure(kwargs, scen["lora"])


def init(key):
    """The trained leaves: both factors of every adapter seeded non-zero,
    so that both have a gradient at the first step."""
    out = {}
    for i, (name, shape) in enumerate(sorted(SHAPES.items())):
        fan_in = shape[0] if name.endswith(".A") else 4 * shape[0]
        out[name] = jax.random.normal(
            jax.random.fold_in(key, i), shape, F32) / math.sqrt(fan_in)
    return out


# --------------------------------------------------------------------------
# pieces


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def swiglu(gate_up):
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return jax.nn.silu(gate) * up


def make_dense(p, w, q):
    """``dense(name, x) = x W + (alpha / rank) (x A) B`` where the leaf
    has an adapter, ``x W`` elsewhere, ``+ b`` where the leaf has a bias;
    ``q`` on every operand."""
    s = SIZES["alpha"] / SIZES["rank"]

    def dense(name, x):
        y = jnp.dot(q(x), q(w[name].astype(F32)), precision=HI)
        if name + ".A" in p:
            xa = jnp.dot(q(x), q(p[name + ".A"]), precision=HI)
            y = y + s * jnp.dot(q(xa), q(p[name + ".B"]), precision=HI)
        if name + ".bias" in w:
            y = y + w[name + ".bias"].astype(F32)
        return y

    return dense


def kda_recurrence(q, k, v, g, beta, block=64):
    """``S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_(t-1) + b_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, a position at a time. ``q, k, g`` [B, T, H, K],
    ``v`` [B, T, H, V], ``beta`` [B, T, H]."""
    B, T, H, K = q.shape
    pad = -T % block
    if pad:  # k = 0, b = 0, no decay: the state passes
        longer = lambda a: jnp.pad(
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = map(longer, (q, k, v, g, beta))

    def position(S, xs):
        # with a = e^g: k^T (Diag(a) S) = (a k)^T S, and S_t^T q = (a q)^T
        # S + (q . k) u: both products read the state before the step, once
        q_t, k_t, v_t, g_t, b_t = xs
        a = jnp.exp(g_t)
        kS, qS = jnp.moveaxis(jnp.einsum(
            "bhnk,bhkv->bhnv", jnp.stack([a * k_t, a * q_t], axis=2), S,
            precision=HI), 2, 0)
        u = b_t[..., None] * (v_t - kS)
        o = qS + jnp.sum(q_t * k_t, axis=-1, keepdims=True) * u
        return a[..., None] * S + k_t[..., None] * u[..., None, :], o

    @jax.checkpoint
    def span(S, xs):  # UNROLL positions a step of the loop: the same sums
        return jax.lax.scan(position, S, xs, unroll=UNROLL)

    by_block = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        (-1, block) + a.shape[:1] + a.shape[2:])
    _, o = jax.lax.scan(span, jnp.zeros((B, H, K, v.shape[-1]), F32),
                        tuple(map(by_block, (q, k, v, g, beta))))
    o = o.reshape((-1,) + o.shape[2:])  # [T, B, H, V]
    return jnp.moveaxis(o, 0, 1)[:, :T]


def kda_inputs(dense, w, L, x):
    """What the recurrence takes, and the output gate: ``q, k, v`` after
    the short convolution and SiLU, q and k of unit length a head, the
    log-decay a channel, beta a head, the gate a channel."""
    z = SIZES
    B, T, _ = x.shape
    H, K = z["heads"], z["head_dim"]
    heads = lambda a: a.reshape(B, T, H, K)

    def conv_silu(name):
        y = dense(L + "kda_" + name, x)
        taps = w[L + name + "_conv"].astype(F32)
        n = taps.shape[0]
        yp = jnp.pad(y, ((0, 0), (n - 1, 0), (0, 0)))
        y = sum(yp[:, i:i + T] * taps[i] for i in range(n))
        return heads(jax.nn.silu(y))

    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    qq, kk, vv = conv_silu("q"), conv_silu("k"), conv_silu("v")
    qq, kk = unit(qq) * K ** -0.5, unit(kk)
    pre = dense(L + "kda_fb", dense(L + "kda_fa", x)) \
        + w[L + "dt_bias"].astype(F32)
    g = -jnp.exp(w[L + "A_log"].astype(F32))[:, None] \
        * jax.nn.softplus(heads(pre))
    beta = jax.nn.sigmoid(dense(L + "kda_b", x))
    gate = jax.nn.sigmoid(heads(dense(L + "kda_gb", dense(L + "kda_ga", x))))
    return qq, kk, vv, g, beta, gate


def kda_output(dense, w, L, o, gate):
    B, T, H, K = o.shape
    o = rms_norm(o, w[L + "o_norm"], SIZES["eps"]) * gate
    return dense(L + "kda_o", o.reshape(B, T, H * K))


def masked_attention(qh, kh, vh, scale, q):
    """Softmax attention of ``qh`` [B, T, H, D] over ``kh`` [B, T, H, D],
    ``vh`` [B, T, H, Dv], key ``j`` allowed for query ``i`` where ``j <=
    i``. A block of queries at a time, each against the keys up to the
    end of its group of blocks (``KEY_GROUPS`` groups: past that end
    every key is masked for every query of the group)."""
    B, T, H, D = qh.shape
    divisor = lambda n, most: max(b for b in range(1, min(most, n) + 1)
                                  if n % b == 0)
    blk = divisor(T, QUERY_BLOCK)
    groups = divisor(T // blk, KEY_GROUPS)
    per = T // blk // groups  # query blocks a group
    kq, vq = q(kh), q(vh)

    def block(n_keys, args):
        qb, start = args  # [B, blk, H, D], first position
        i = (start + jnp.arange(blk))[:, None]
        allowed = jnp.arange(n_keys)[None, :] <= i
        s = jnp.einsum("bqhd,bkhd->bhqk", q(qb), kq[:, :n_keys],
                       precision=HI) * scale
        pr = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", q(pr), vq[:, :n_keys],
                          precision=HI)

    out = []
    for g in range(groups):
        first = g * per
        rows = qh[:, first * blk:(first + per) * blk]
        out.append(jax.lax.map(
            jax.checkpoint(functools.partial(block, (first + per) * blk)),
            (jnp.moveaxis(rows.reshape(B, per, blk, H, D), 1, 0),
             (first + jnp.arange(per)) * blk)))
    o = jnp.concatenate(out, axis=0)  # [T / blk, B, blk, H, Dv]
    return jnp.moveaxis(o, 0, 1).reshape(B, T, H, vh.shape[-1])


def mla_mixer(dense, w, L, x, q):
    z = SIZES
    B, T, _ = x.shape
    H, N, R, Dv, C = z["heads"], z["nope"], z["rope"], z["v_dim"], z["kv_rank"]
    qh = dense(L + "mla_q", x).reshape(B, T, H, N + R)
    ckr = dense(L + "mla_dkv", x)
    c = rms_norm(ckr[..., :C], w[L + "c_norm"], z["eps"])
    kv = dense(L + "mla_ukv", c).reshape(B, T, H, N + Dv)
    kh = jnp.concatenate([kv[..., :N], jnp.broadcast_to(
        ckr[:, :, None, C:], (B, T, H, R))], axis=-1)
    o = masked_attention(qh, kh, kv[..., N:], (N + R) ** -0.5, q)
    return dense(L + "mla_o", o.reshape(B, T, H * Dv))


def route(x, router, bias, q):
    """Sigmoid scores over all experts, the ``top_k`` largest of score
    plus bias, ``w = scale s / sum_chosen s``. Returns the weights as a
    dense [rows, experts] matrix, 0 where an expert is not chosen."""
    z = SIZES
    s = jax.nn.sigmoid(jnp.dot(q(x), q(router.astype(F32)), precision=HI))
    n, e = s.shape
    _, idx = jax.lax.top_k(s + bias.astype(F32), z["top_k"])
    chosen = jnp.zeros((n, e), bool).at[jnp.arange(n)[:, None], idx].set(True)
    w = jnp.where(chosen, s, 0.0)
    return z["route_scale"] * w / jnp.sum(w, axis=1, keepdims=True)


def held_experts(x, w_all, gate_up, down, q, offset):
    """``sum over the held experts chosen of w_i E_i(x)`` for rows ``x``
    [n, d]; ``E(x) = W_d (SiLU(W_g x) * W_u x)``. An expert at a time,
    over the rows that chose it, gathered into the smallest of the sizes
    its even share times 1, 2, 4, .. (every row the last) that holds them."""
    n, d = x.shape
    held = gate_up.shape[0]
    w_held = w_all[:, offset:offset + held]
    share = -(-n * SIZES["top_k"] // w_all.shape[1])
    caps = [share << i for i in range(n.bit_length())
            if share << i < n] + [n]
    xz = jnp.concatenate([x, jnp.zeros((1, d), F32)])

    def through(rows, gu, dn):
        h = swiglu(jnp.dot(q(rows), q(gu.astype(F32)), precision=HI))
        return jnp.dot(q(h), q(dn.astype(F32)), precision=HI)

    def chosen_rows(cap, w_e, gu, dn):
        rows = jnp.nonzero(w_e > 0, size=cap, fill_value=n)[0]
        we = jnp.concatenate([w_e, jnp.zeros((1,), F32)])[rows]
        return jnp.zeros((n + 1, d), F32).at[rows].add(
            through(xz[rows], gu, dn) * we[:, None])[:n]

    @jax.checkpoint  # on the way back an expert at a time again
    def one(args):
        short = jnp.sum(jnp.sum(args[0] > 0) > jnp.asarray(caps[:-1]))
        return jax.lax.switch(short, [functools.partial(chosen_rows, cap)
                                      for cap in caps], *args)

    def expert(acc, args):
        return acc + one(args), None

    return jax.lax.scan(expert, jnp.zeros((n, d), F32),
                        (w_held.T, gate_up, down))[0]


def expert_ffn(dense, w, L, x, q):
    B, T, d = x.shape
    rows = x.reshape(B * T, d)
    w_all = route(rows, w[L + "router"], w[L + "router_bias"], q)
    y = held_experts(rows, w_all, w[L + "experts_gate_up"],
                     w[L + "experts_down"], q, SIZES["expert_offset"])
    shared = dense(L + "shared_down", swiglu(dense(L + "shared_gate_up", x)))
    return y.reshape(B, T, d) + shared


def forward(p, x, q=lambda a: a, frozen=None):
    """Token ids [B, T] -> logits [B, T, vocab], float32."""
    z, w = SIZES, frozen

    def make_layer(L, mixer, experts):
        def ffn(h, dense):
            hn = rms_norm(h, w[L + "ffn_norm"], z["eps"])
            if experts:
                return h + expert_ffn(dense, w, L, hn, q)
            return h + dense(L + "ffn_down",
                             swiglu(dense(L + "ffn_gate_up", hn)))

        def layer(h, p):
            dense = make_dense(p, w, q)
            hn = rms_norm(h, w[L + "mixer_norm"], z["eps"])
            return ffn(h + mla_mixer(dense, w, L, hn, q), dense)

        @jax.checkpoint  # on the way back what the recurrence takes is
        # formed anew, and only it is kept across the recurrence's own
        def inputs_of(hn, p):
            return kda_inputs(make_dense(p, w, q), w, L, hn)

        def kda_layer(h, p):
            dense = make_dense(p, w, q)
            hn = rms_norm(h, w[L + "mixer_norm"], z["eps"])
            *inputs, gate = inputs_of(hn, p)
            o = kda_recurrence(*inputs)
            return ffn(h + kda_output(dense, w, L, o, gate), dense)

        # one layer's activations at a time: a KDA layer's recurrence runs
        # once more on the way back (at 16,384 positions the inputs it
        # would keep instead, a layer's q, k, v, decay and gate, are 1.3
        # GB, and the six layers' did not fit the chip)
        return jax.checkpoint(layer if mixer == "mla" else kda_layer)

    h = w["embed"].astype(F32)[x.astype(jnp.int32)]
    for l, mixer, experts in z["kinds"]:
        h = make_layer(f"L{l}.", mixer, experts)(h, p)
    h = rms_norm(h, w["final_norm"], z["eps"])
    return make_dense(p, w, q)("head", h)


def loss(logits, y, mask):
    """Mean over the kept rows of each row's mean cross-entropy over its
    positions; ``y`` holds a label a position."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, y[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    per_row = jnp.mean(lse - ll, axis=-1)
    m = mask.astype(F32)
    return jnp.sum(per_row * m) / jnp.maximum(jnp.sum(m), 1.0)


_configure_from_file()
