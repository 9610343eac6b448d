"""Ling-3.0-flash (``bailing_hybrid``): a hybrid language model of
linear-attention (KDA) and latent-attention (MLA) mixers over dense and
group-limited sparse-expert feed-forward layers.

Pre-norm residual blocks, ``x += Mixer(RMSNorm(x)); x += FFN(RMSNorm(x))``,
RMSNorm eps 1e-6, no biases, a final RMSNorm and an untied head. Layer
``i`` of the published stack has an MLA mixer where ``(i + 1) %
layer_group_size == 0`` and a KDA mixer otherwise; a dense SwiGLU FFN
where ``i < first_k_dense_replace`` and the expert FFN otherwise. Every
size is a keyword argument: the published widths come from the
scenario's ``model.kwargs`` (``benchmark/configs/ling-3.0-flash.json``),
the defaults are a toy for the CPU tests.

Meant to be trained as a FROZEN base under per-node adapters
(``learning/lora.py``): the dense projections are ``nn.Dense`` modules,
which is where an adapter rides; the expert layer's grouped products see
the tokens of all nodes of a federation's step together
(``ops/fold.py``). The equations, each departure from the published
description and what the public config leaves open are written down in
``benchmark/reference/ling_flash.py``, the plain reference this module
is compared with.

One chip holds its share of a deployment: ``experts_held`` of the
``n_experts`` the router scores (one routing group: experts
``expert_offset ..``), and a slice of the vocabulary. The layer routes
over all experts and computes its own experts' part of the result; what
the absent experts would add is left out. No chosen (token, held
expert) pair is dropped, whatever the imbalance. The frozen layer keeps
nothing for the way back: each block of sorted pairs forms its
up-projection again and goes back by hand in three grouped products
(``_block_back``), the down-projection's output never formed a second
time: a training step executes 8/3 of a forward's expert FLOPs.

``models/laguna.py``, ``models/lfm2.py``, ``models/kimi_linear.py`` and
``models/qwen3_next.py`` build their models from the parts here that are
not Ling's alone:
``causal_attention`` (its window, its grouped query heads and its scope
are arguments; on the TPU it runs the fused kernels of
``ops/flash_attention.py``, elsewhere its own tiled form), ``ExpertFFN``
over ``held_experts`` (the router is an argument), ``RMSNorm``,
``DenseFFN``, ``CausalLM`` (the embedding, the head, the chunked loss,
the counters), ``causal_conv``; Kimi Linear's also ``head_blocks``,
``kda_heads`` (its ``floor`` chooses the form: Ling's gate is bounded
below, Kimi Linear's is not; on the TPU the unbounded form's diagonal
sub-blocks are the kernels of ``ops/kda_diag.py``) and ``MLAMixer``
(without its rotary embedding, q/k norms and gate); Qwen3-Next's
``head_blocks`` and ``kda_heads`` with a decay a head (its scalar form,
key heads fewer than value heads), ``causal_attention`` at heads of 256
and ``ExpertFFN`` with a gate on its shared expert. A change to one of
them is measured in every cell that uses it.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from p2pfl_tpu.models.base import register_lora_targets, register_model
from p2pfl_tpu.ops import kda_diag
from p2pfl_tpu.ops.flash_attention import flash_attention, padded, visits
from p2pfl_tpu.ops.fold import fold_rows

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
#: the delta rule's sub-chunk, and the largest exponent its bounded form
#: allows inside one (Ling's gate: 16 steps of at most 5)
SUB, CLIP = 16, 80.0


def _dense(features, name, mod):
    return nn.Dense(features, use_bias=False, dtype=mod.dtype,
                    param_dtype=mod.param_dtype, name=name,
                    kernel_init=nn.initializers.lecun_normal())


def rms_norm(x, scale, eps):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * scale.astype(F32)


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        return rms_norm(x, scale, self.eps).astype(self.dtype)


def swiglu(gate_up):
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return jax.nn.silu(gate) * up


# --------------------------------------------------------------------------
# Kimi Delta Attention, chunk-wise


def head_blocks(core, head_block, rows, frozen=()):
    """``core(*rows, *frozen)`` over ``head_block`` heads at a time (axis 2
    of every array), one block after the other and each recomputed on the
    way back: heads do not meet in the delta rule, and its float32
    intermediates for all 32 heads of 8 nodes' sequences are more than a
    chip holds.

    ``rows`` lead with the batch; ``frozen`` is what every row shares
    (the base's taps and norm scale, a leading axis of 1). On a FORWARD
    pass under the round's ``vmap`` over nodes THE NODES ARE THE BATCH:
    the blocks run once, on the rows of all nodes laid end to end
    (``ops/fold.py``), and a mapped ``frozen`` is refused. A ``vmap`` of
    the delta rule's scan puts the node axis behind the chunk axis again
    (``[NC, n, 1, H, C, K]`` from ``[n, NC, 1, ..]``) and brings back the
    transposing copies that the chunk-first layout of :func:`kda_heads`
    saves. Forward passes are two of a training step's three and all of
    an evaluation's. The way back is reverse mode through the plain loop,
    each block under ``jax.checkpoint``, under the caller's own ``vmap``:
    folded too it ran no faster on the v5e and needed 245 MB more of
    temporaries in a round program that stands at the chip's limit
    (``PERF.md`` Findings PR 40), and so ``frozen`` takes each node's
    own gradient."""
    H = rows[0].shape[2]
    cut = lambda a: jnp.moveaxis(a.reshape(
        a.shape[:2] + (H // head_block, head_block) + a.shape[3:]), 2, 0)

    def blocks(rows, frozen):
        if H <= head_block or H % head_block:
            return core(*rows, *frozen)
        o = jax.lax.map(
            lambda args: jax.checkpoint(core)(*args[0], *args[1]),
            jax.tree.map(cut, (rows, frozen)))
        o = jnp.moveaxis(o, 0, 2)  # [B, T, blocks, head_block, V]
        return o.reshape(o.shape[:2] + (H, o.shape[-1]))

    once = fold_rows(blocks, lambda out: True)

    @jax.custom_vjp
    def folded(rows, frozen):
        return once(rows, frozen)

    # the way back keeps the inputs alone; ``vjp``'s own forward loop has
    # no reader (its blocks keep nothing) and XLA drops it
    folded.defvjp(lambda rows, frozen: (once(rows, frozen), (rows, frozen)),
                  lambda kept, g: jax.vjp(blocks, *kept)[1](g))
    return folded(tuple(rows), tuple(frozen))


def unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower-triangular ``A`` [..., C, C],
    ``C`` a power of two, by block forward substitution: ``[[L11, 0],
    [L21, L22]]^-1 = [[T11, 0], [-T22 L21 T11, T22]]``, all diagonal
    blocks of one size at a time from size 1 (the diagonal is 1) up.

    The systems lie along the last axis meanwhile (``[C, C, systems]``)
    and a product of blocks is a float32 multiply and sum over whole
    vectors of systems, not a batched matmul: at ``HIGHEST`` the v5e's
    matrix unit takes longer over a system's 16- or 32-row blocks than
    over its 64 rows at once, and the merges ran 2 to 8 times slower
    there (``PERF.md`` Findings PR 36)."""
    C = A.shape[-1]
    if C & (C - 1):
        raise ValueError(f"a chunk of {C} rows cannot be halved down to 1: "
                         "the chunk length has to be a power of two")
    L = jnp.moveaxis(A.reshape((-1, C, C)), 0, -1)
    n = L.shape[-1]
    product = lambda X, Y: jnp.sum(X[:, :, :, None] * Y[:, None], axis=2)
    T = jnp.ones((C, 1, 1, n), F32)  # [blocks, s, s, systems]
    s = 1
    while s < C:
        # lax's own slice and concatenate: 63 blocks are cut out of L a
        # call, and a jnp index costs several times as much to trace
        L21 = jax.lax.concatenate(
            [jax.lax.slice(L, (b + s, b, 0), (b + 2 * s, b + s, n))
             for b in range(0, C, 2 * s)], 0).reshape(-1, s, s, n)
        T = T.reshape(-1, 2, s, s, n)
        T11, T22 = T[:, 0], T[:, 1]
        T21 = -product(T22, product(L21, T11))
        T = jax.lax.concatenate([
            jax.lax.concatenate([T11, jnp.zeros_like(T11)], 2),
            jax.lax.concatenate([T21, T22], 2)], 1)
        s *= 2
    return jnp.moveaxis(T[0], -1, 0).reshape(A.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def unit_lower_solve(A, rhs, scope="kda.scan"):
    """``X`` of ``(I + A) X = rhs`` for strictly lower-triangular ``A``
    [..., C, C] and ``rhs`` [..., C, W], float32: the inverse
    (:func:`unit_lower_inverse`), then one product at ``HIGHEST``.
    ``rhs`` may be a tuple of right-hand sides, which share the one
    inverse and take a product each: side by side in one ``[.., C, 2
    K]`` array they are a concatenation before and two slices after, a
    pass over each in HBM that computes nothing. The way back keeps the
    inverse and every ``X`` and is two more such products a right-hand
    side: ``d_rhs = T^T g``, ``d_A = -sum d_rhs X^T`` below the
    diagonal. ``scope`` is the one the caller opens around the delta
    rule, which the hand-written way back sets by hand."""
    return _solve(A, rhs, scope)[0]


def _solve(A, rhs, scope):
    with jax.named_scope("kda.solve"):
        T = unit_lower_inverse(A)
        X = jax.tree.map(lambda r: jnp.matmul(T, r, precision=HI), rhs)
    return X, (T, X)


def _solve_back(scope, kept, g):
    T, X = kept
    # a hand-written way back does not inherit the name stack of the way
    # forward: the scopes the device time is read by are set here
    with jax.named_scope(scope), jax.named_scope("kda.solve"):
        d_rhs = jax.tree.map(lambda g_: jnp.einsum(
            "...ji,...jw->...iw", T, g_, precision=HI), g)
        d_A = -jnp.tril(sum(
            jnp.einsum("...iw,...jw->...ij", d, x, precision=HI)
            for d, x in zip(jax.tree.leaves(d_rhs), jax.tree.leaves(X))), -1)
    return d_A, d_rhs


unit_lower_solve.defvjp(_solve, _solve_back)


def _sub_rows(a, sub):
    """[.., C, K] -> [.., C / sub, sub, K]"""
    return a.reshape(a.shape[:-2] + (a.shape[-2] // sub, sub, a.shape[-1]))


def _decays(G, sub):
    """``e^(G_i - G_j)`` of every row ``i`` of each sub-chunk against its
    every key ``j``, ``[.., sub (i), sub (j), K]``, 0 where ``j > i``:
    every exponent ``<= 0``. Only ever an operand of a sum over ``K`` or
    over a side of the block, which XLA forms inside the sum's fusion:
    the compiled program holds no such array
    (``tests/test_tpu_aot_compile.py``)."""
    i = jnp.arange(sub)
    seen = (i[:, None] >= i[None, :])[:, :, None]
    return jnp.exp(jnp.where(seen, G[..., :, None, :] - G[..., None, :, :],
                             -jnp.inf))


#: what :func:`kda_heads` traced, by the caller's scope (the one record):
#: ``{"forms": {"scalar" | "channel": traces}, "chunk", "sub", "diag"}``,
#: the last three the last trace's, ``diag`` how its diagonal sub-blocks
#: were formed (``"kernel"``, ``"xla"``, or None where the form has none);
#: the scope traced last is the record's last
_chunk_forms: dict[str, dict[str, Any]] = {}


def chunk_forms() -> dict[str, dict[str, Any]]:
    """The intra-chunk forms :func:`kda_heads` traced, by the caller's
    scope: how many traces took each form (a decay a head: ``scalar``; a
    channel: ``channel``) since the process started, and the last trace's
    chunk, sub-chunk and diagonal blocks. Recorded at trace time, as
    :func:`score_tiles` is."""
    return {scope: dict(rec, forms=dict(rec["forms"]))
            for scope, rec in _chunk_forms.items()}


def diag_forms() -> dict[str, Any]:
    """The last :func:`_diag_pairs` traced, from :func:`chunk_forms`:
    ``{"kernel": whether the Pallas kernels of ``ops/kda_diag.py`` form
    the blocks, "sub": the sub-chunk's rows, "chunk": the chunk's}``;
    empty where none was."""
    for rec in reversed(_chunk_forms.values()):
        if rec["diag"]:
            return {"kernel": rec["diag"] == "kernel", "sub": rec["sub"],
                    "chunk": rec["chunk"]}
    return {}


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _diag_pairs(q, k, G, sub, kernel=False):
    """The diagonal ``sub x sub`` blocks of :func:`kda_heads`' two
    intra-chunk matrices without ``b``, from the pairwise differences of
    the running sums: ``[.., C / sub, sub (i), sub (j)]`` of ``sum_c k_ic
    k_jc e^(G_ic - G_jc)`` and of ``sum_c q_ic k_jc e^(G_ic - G_jc)`` for
    ``j <= i``, 0 above the diagonal; ``q, k, G`` [.., C, K] float32. The
    way back keeps the inputs alone and forms the factors again. By one
    of two forms, as ``kernel`` (static, the platform's: :func:`on_tpu`)
    says:

    - the Pallas kernels of ``ops/kda_diag.py``, one a direction: a
      sub-chunk's factors are formed once in VMEM and every sum of the
      direction is taken from them;
    - the XLA form (the CPU's, and the kernels' parity oracle):
      :func:`_diag_pairs_fwd`, one sum over ``K`` of the factors
      (:func:`_decays`) a block, and :func:`_diag_pairs_back`, which forms
      them again inside each of its five sums. ``sub`` times ``q``'s size
      as a whole array, they never are one (formed a key at a time
      instead, XLA kept the 16 keys' factors of a block of heads for the
      way back, 3.1 GB more than the round program of the chip's share
      had, ``PERF.md`` Findings, Kimi Linear)."""
    return _diag_pairs_kept(q, k, G, sub, kernel)[0]


def _diag_pairs_kept(q, k, G, sub, kernel):
    if kernel:
        return kda_diag.forward(q, k, G, sub=sub), (q, k, G)
    return _diag_pairs_fwd(q, k, G, sub)


def _diag_pairs_fwd(q, k, G, sub):
    qs, ks, Gs = (_sub_rows(a, sub) for a in (q, k, G))
    e = _decays(Gs, sub)
    kj = ks[..., None, :, :] * e
    return (jnp.sum(ks[..., :, None, :] * kj, axis=-1),
            jnp.sum(qs[..., :, None, :] * kj, axis=-1)), (q, k, G)


def _diag_pairs_grads(sub, kernel, kept, grads):
    # a hand-written way back does not inherit the name stack of the way
    # forward: the scopes the device time is read by are set here
    with jax.named_scope("kda.scan"), jax.named_scope("kda.diag"):
        if kernel:
            return kda_diag.backward(*kept, *grads, sub=sub)
        return _diag_pairs_back(sub, kept, grads)


def _diag_pairs_back(sub, kept, grads):
    qs, ks, Gs = (_sub_rows(a, sub) for a in kept)
    d_a, d_b = (d[..., None] for d in grads)  # [.., i, j, 1]
    e = _decays(Gs, sub)
    kj = ks[..., None, :, :]
    # the products' gradients to ``k_i`` and ``q_i`` (rows), to the
    # factor ``k_j e_ij`` (``w``), and through ``e_ij`` to ``G_i`` and
    # ``G_j``
    dk = jnp.sum(d_a * kj * e, axis=-2)
    dq = jnp.sum(d_b * kj * e, axis=-2)
    w = (d_a * ks[..., :, None, :] + d_b * qs[..., :, None, :]) * e
    dk = dk + jnp.sum(w, axis=-3)
    t = w * kj
    dG = jnp.sum(t, axis=-2) - jnp.sum(t, axis=-3)
    whole = lambda a: a.reshape(kept[0].shape)
    return whole(dq), whole(dk), whole(dG)


_diag_pairs.defvjp(_diag_pairs_kept, _diag_pairs_grads)


def kda_chunked(q, k, v, g, beta, chunk=64, sub=SUB, dtype=jnp.bfloat16,
                head_block=8, floor=None):
    """:func:`kda_heads`, a block of heads at a time."""
    return head_blocks(
        functools.partial(kda_heads, chunk=chunk, sub=sub, dtype=dtype,
                          floor=floor),
        head_block, (q, k, v, g, beta))


def kda_heads(q, k, v, g, beta, chunk=64, sub=SUB, dtype=jnp.bfloat16,
              floor=None, scope="kda.scan"):
    """The delta rule with a decay per channel or per head, a chunk at a
    time.

    ``q, k`` [B, T, Hk, K] (normalised, q scaled), ``v`` [B, T, H, V],
    ``g`` [B, T, H, K] or [B, T, H] the log-decay (finite, ``<= 0``),
    ``beta`` [B, T, H]. Value head ``h`` reads key head ``h // (H /
    Hk)``; with a decay a channel ``Hk`` is ``H``. Per head: ``S_t = (I -
    b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T``, ``o_t = S_t^T
    q_t``. Within a chunk, with ``G`` the running sum of ``g`` and ``S_0``
    the state the chunk starts from, the updates ``u_i = b_i (v_i -
    k_i^T Diag(a_i) S_{i-1})`` solve the unit lower-triangular system
    ``(I + A) U = b (V - (K e^G) S_0)``, ``A_ij = b_i sum_c k_ic k_jc
    e^(G_ic - G_jc)`` for ``j < i``; then ``O = (Q e^G) S_0 + B U`` with
    ``B_ij = sum_c q_ic k_jc e^(G_ic - G_jc)`` for ``j <= i``, and ``S_C
    = e^(G_C) S_0 + (K e^(G_C - G))^T U``.

    A decay with no channel axis (one number a value head and position,
    Gated DeltaNet's) takes the scalar form (:func:`_scalar_pairs`):
    ``e^(G_i - G_j)`` leaves the sum over channels, so ``A`` and ``B`` are
    ``K K^T`` and ``Q K^T``, one product a KEY head over the whole chunk,
    times the ``[C, C]`` matrix of pairwise decays of each value head;
    every exponent ``<= 0``, exact for any finite decay, no sub-chunks.
    The per-channel forms follow.

    ``e^(G_i - G_j)`` is never formed from ``e^(-G_j)`` alone, which
    overflows within a chunk: rows are taken a sub-chunk of ``sub`` at a
    time against the running sum ``ref`` at that sub-chunk's start, as
    ``e^(G_i - ref) e^(ref - G_j)``, both exponents ``<= 0`` for a key
    ``j`` before the sub-chunk. Inside it, where ``ref - G_j`` is the
    decay over up to ``sub`` steps, ``floor`` (static, the model's own, as
    ``window`` is attention's) chooses one of two forms:

    - a gate bounded below by ``floor`` with ``sub * -floor <= 80``
      (Ling's safe gate, -5 over 16 steps): the same product over the
      whole row of the sub-chunk, the second exponent clipped at 80,
      which it never reaches (at Ling's shapes a third fewer bytes
      accessed than the other form, forward and back);
    - any other ``floor``, ``None`` among them (Kimi Linear's softplus
      gate, unbounded below): the diagonal ``sub x sub`` blocks from the
      pairwise differences ``e^(G_i - G_j)``, ``j <= i``, which never
      exceed 1, float32 (:func:`_diag_pairs`, scope ``kda.diag``): exact
      for any finite decay; the ``[.., sub, sub, K]`` factors are never
      an array: on the TPU the kernels of ``ops/kda_diag.py`` form them
      in VMEM, elsewhere they exist only inside the fusions of the sums
      over them.

    The system is solved by forming ``(I + A)^-1`` block by block and one
    product with the right-hand side (:func:`unit_lower_solve`; ``chunk``
    a power of two), not by the powers of ``A``: with ``|A_ij|`` up to
    ``b_i`` < 1 they reach binomial size over 64 rows and cancel in
    float32. The state, the running sums, the exponentials and the solve
    are float32 whatever ``dtype`` is; the other products take ``dtype``
    operands and accumulate in float32. ``scope`` is the one the caller
    opens around this call (the solve's hand-written way back sets it);
    the form each call traced is recorded by it (:func:`chunk_forms`)."""
    B, T = q.shape[:2]
    C = chunk
    scalar = g.ndim == 3
    bounded = floor is not None and sub * -floor <= CLIP
    kernel = on_tpu()
    record = _chunk_forms.pop(scope, {"forms": {}})
    form = "scalar" if scalar else "channel"
    record["forms"][form] = record["forms"].get(form, 0) + 1
    record.update(chunk=C, sub=sub, diag=None if scalar or bounded
                  else "kernel" if kernel else "xla")
    _chunk_forms[scope] = record
    pad = -T % C
    if pad:  # zero keys, no decay, beta 0: the state passes unchanged
        longer = lambda a: jnp.pad(
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = map(longer, (q, k, v, g, beta))
    NC = (T + pad) // C
    # [B, T, H, ..] -> [NC, B, H, C, ..]: the one transposing copy in
    chunks = lambda a: jnp.moveaxis(a.astype(F32).reshape(
        (B, NC, C) + a.shape[2:]), (1, 3), (0, 2))
    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=3)
    mm = functools.partial(jnp.einsum, preferred_element_type=F32)
    lo = lambda a: a.astype(dtype)
    if scalar:
        A, Bm = _scalar_pairs(q, k, G, beta, lo, mm)
        # what follows is elementwise a value head: each reads its key
        # head, and the decay is one column
        q, k = (jnp.repeat(a, v.shape[2] // a.shape[2], axis=2)
                for a in (q, k))
        return _across_chunks(q, k, v, G[..., None], beta, A, Bm, lo, mm,
                              scope)[:, :T]
    if not bounded:
        with jax.named_scope("kda.diag"):
            diag_a, diag_b = _diag_pairs(q, k, G, sub, kernel)

    # ---- the two intra-chunk matrices, a sub-chunk of rows at a time
    rows_a, rows_b = [], []
    for a in range(C // sub):
        s0, s1 = a * sub, (a + 1) * sub
        ref = G[..., s0 - 1:s0, :] if a else jnp.zeros_like(G[..., :1, :])
        left = jnp.exp(G[..., s0:s1, :] - ref)  # exponents <= 0
        widen = lambda m: jnp.pad(m, ((0, 0),) * 4 + ((0, C - s1),))
        if not bounded:
            # keys before the sub-chunk on the matrix unit (none in the
            # first), its own from the pairwise differences; one trace a
            # sub-chunk, every shape static
            right = lo(k[..., :s0, :] * jnp.exp(ref - G[..., :s0, :]))
            for x, rows, diag in ((k, rows_a, diag_a), (q, rows_b, diag_b)):
                before = mm("nbhik,nbhjk->nbhij", lo(x[..., s0:s1, :] * left),
                            right)
                rows.append(widen(jnp.concatenate(  # fedlint: disable=recompile-hazard
                    [before, diag[..., a, :, :]], axis=-1)))
            continue
        right = lo(k[..., :s1, :] * jnp.exp(jnp.minimum(
            ref - G[..., :s1, :], CLIP)))  # <= 0 before s0, <= 80 inside
        rows_a.append(widen(mm("nbhik,nbhjk->nbhij",
                               lo(k[..., s0:s1, :] * left), right)))
        rows_b.append(widen(mm("nbhik,nbhjk->nbhij",
                               lo(q[..., s0:s1, :] * left), right)))
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    A = jnp.where(j < i, jnp.concatenate(rows_a, axis=3), 0.0) * beta[..., None]
    Bm = jnp.where(j <= i, jnp.concatenate(rows_b, axis=3), 0.0)
    return _across_chunks(q, k, v, G, beta, A, Bm, lo, mm, scope)[:, :T]


def _scalar_pairs(q, k, G, beta, lo, mm):
    """:func:`kda_heads`' two intra-chunk matrices for a decay that is
    one number a value head: ``q, k`` [NC, B, Hk, C, K], ``G``, ``beta``
    [NC, B, H, C]; returns ``A`` and ``B`` [NC, B, H, C, C]. ``K K^T``
    and ``Q K^T`` are one product a key head, whole chunks on the matrix
    unit; the pairwise decays ``e^(G_i - G_j)``, ``j <= i``, are one
    ``[C, C]`` matrix a value head, which never exceeds 1."""
    NC, B, Hk, C, _ = q.shape
    H = G.shape[2]
    by_key = lambda a: a.reshape((NC, B, Hk, H // Hk) + a.shape[3:])
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    Gk = by_key(G)
    D = jnp.exp(jnp.where(j <= i, Gk[..., :, None] - Gk[..., None, :],
                          -jnp.inf))
    kk, qk = (mm("nbhik,nbhjk->nbhij", lo(x), lo(k))[:, :, :, None]
              for x in (k, q))
    A = jnp.where(j < i, kk * D, 0.0) * by_key(beta)[..., None]
    whole = lambda m: m.reshape((NC, B, H, C, C))
    return whole(A), whole(qk * D)


def _across_chunks(q, k, v, G, beta, A, Bm, lo, mm, scope):
    """The rest of :func:`kda_heads`, every array a value head's: the
    solve within each chunk and the recurrence over chunks. ``G`` is the
    running log-decay [NC, B, H, C, K], or [.., C, 1] a head; returns
    [B, NC x C, H, V]."""
    NC, B, H, C, K = k.shape
    # ---- (I + A) Wv = beta V, (I + A) Wk = beta K e^G
    Wv, Wk = unit_lower_solve(
        A, (v * beta[..., None], k * jnp.exp(G) * beta[..., None]), scope)
    Qp = q * jnp.exp(G)
    G_end = G[..., -1:, :]
    Kd = k * jnp.exp(G_end - G)
    decay = jnp.exp(G_end[..., 0, :])  # [NC, B, H, K], or [.., 1]

    # ---- the recurrence over chunks
    def step(S, xs):
        Wv_c, Wk_c, Qp_c, B_c, Kd_c, decay_c = xs
        U = Wv_c - mm("bhck,bhkv->bhcv", lo(Wk_c), lo(S))
        O = mm("bhck,bhkv->bhcv", lo(Qp_c), lo(S)) \
            + mm("bhij,bhjv->bhiv", lo(B_c), lo(U))
        S = decay_c[..., None] * S + mm("bhck,bhcv->bhkv", lo(Kd_c), lo(U))
        return S, O

    _, O = jax.lax.scan(step, jnp.zeros((B, H, K, v.shape[-1]), F32),
                        (Wv, Wk, Qp, Bm, Kd, decay))
    # [NC, B, H, C, V] -> [B, T, H, V]: the one transposing copy out
    return jnp.moveaxis(O, (0, 2), (1, 3)).reshape(B, NC * C, H, -1)


def causal_conv(x, taps):
    """Depthwise causal convolution over positions, the convolution
    alone: ``x`` [B, T, D], ``taps`` [k, D], ``y_t = sum_i taps[i] x_(t -
    k + 1 + i)`` with zeros before position 0, no bias, no activation
    (any ``T``, shorter than the taps too). The one causal convolution in
    the tree: KDA's (:func:`causal_conv_silu`) and the short-convolution
    mixer of ``models/lfm2.py`` both call it."""
    k = taps.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    T = x.shape[1]
    return sum(xp[:, i:i + T] * taps[i] for i in range(k))


def causal_conv_silu(x, taps):
    """:func:`causal_conv`, then SiLU."""
    return jax.nn.silu(causal_conv(x, taps))


class KDAMixer(nn.Module):
    heads: int
    head_dim: int
    conv: int = 4
    lower_bound: float = -5.0
    chunk: int = 64
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        """The projections and the output projection are per node under
        the round's ``vmap`` (adapters ride on them); everything between
        them has the base's weights only (the taps, the norm's scale)
        and on a forward pass runs ONCE over the rows of all nodes, a
        block of 4 heads at a time (:func:`head_blocks`): the nodes are
        the delta rule's batch."""
        B, T, _ = x.shape
        H, K = self.heads, self.head_dim
        taps_init = nn.initializers.normal(1.0 / math.sqrt(self.conv))
        heads_of = lambda y: y.reshape(B, T, H, K)
        qkv = [heads_of(_dense(H * K, f"kda_{n}", self)(x)) for n in "qkv"]
        taps = [self.param(f"{n}_conv", taps_init, (self.conv, H * K),
                           self.param_dtype).astype(self.dtype)
                .reshape(1, self.conv, H, K) for n in "qkv"]
        a = heads_of(_dense(H * K, "kda_a", self)(x))
        beta = jax.nn.sigmoid(_dense(H, "kda_b", self)(x).astype(F32))
        gate = jax.nn.sigmoid(_dense(H, "kda_g", self)(x).astype(F32))
        scale = self.param("o_norm", nn.initializers.ones, (K,),
                           self.param_dtype)

        def heads(q, k, v, a, beta, gate, tq, tk, tv, scale):
            # everything between the projections and the output
            # projection, a block of heads at a time: what is float32 (the
            # unit q and k, the log-decay, the state, the output before its
            # norm and gate) is made and used up here
            with jax.named_scope("kda.conv"):
                conv = lambda y, t: causal_conv_silu(
                    y.reshape(y.shape[:2] + (-1,)), t.reshape(self.conv, -1)
                ).reshape(y.shape)
                unit = lambda t: t.astype(F32) * jax.lax.rsqrt(jnp.sum(
                    jnp.square(t.astype(F32)), -1, keepdims=True) + 1e-6)
                q, k, v = conv(q, tq), conv(k, tk), conv(v, tv)
                q, k = unit(q) * K ** -0.5, unit(k)
            g = self.lower_bound * jax.nn.sigmoid(a.astype(F32))
            with jax.named_scope("kda.scan"):
                o = kda_heads(q, k, v, g, beta, chunk=self.chunk,
                              dtype=self.dtype, floor=self.lower_bound)
            return (rms_norm(o, scale, self.eps)
                    * gate[..., None]).astype(self.dtype)

        # the scale is every head's: given a head axis, to be cut like the taps
        o = head_blocks(
            heads, 4, (*qkv, a, beta, gate),
            (*taps, jnp.broadcast_to(scale, (1, 1, H, K))))
        return _dense(x.shape[-1], "kda_o", self)(o.reshape(B, T, H * K))


# --------------------------------------------------------------------------
# latent attention


def rope_interleaved(x, theta):
    """Rotary embedding over the last axis, pairs ``(x_0, x_1), (x_2,
    x_3), ..``; ``x`` [B, T, H, R], positions ``0 .. T - 1``."""
    R = x.shape[-1]
    inv = theta ** (-jnp.arange(0, R, 2, dtype=F32) / R)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


_score_tiles: dict[str, dict[str, int]] = {}


def score_tiles(scope: str = "mla.attn") -> dict[str, int]:
    """The last :func:`causal_attention` traced under ``scope``:
    ``{"computed": score tiles a pass over a sequence forms, "square":
    tiles of the full ``[T, T]`` square, "block", "tile": a tile's rows
    and columns, "kernel": whether the fused kernel forms them}``; empty
    where none was. Recorded at trace time, as ``cnn.lowerings()`` is."""
    return dict(_score_tiles.get(scope, {}))


def on_tpu() -> bool:
    """Whether the programs this process traces run on the TPU, where
    :func:`causal_attention` takes the fused kernel."""
    return jax.default_backend() == "tpu"


def kernel_tiles(T, head_dim):
    """The fused kernel's ``(block, tile)`` at ``T`` positions for heads
    of ``head_dim``, window or none: the fastest forward-and-way-back
    pair of each cell's shapes on the v5e (``PERF.md`` section 5, "since
    PR 44"; every shape measured runs the kernel faster than the tiled
    form, so none keeps it), neither past the power of two at or over
    ``T`` nor under the 128 lanes a tile of scores spans. At heads of 64
    and 128 blocks and tiles of 1024 ran slower; Ling's heads of 192 ran
    fastest at 1024; at Qwen3-Next's heads of 256 (8 query heads a key
    head, 4 x 8192 positions) 512 x 512 took 62 ms forward and back
    against 104 at 512 x 1024 and about 72 at 1024 x 1024 (the cell's own
    trace)."""
    block, tile = (1024, 1024) if 128 < head_dim <= 192 else (512, 512)
    whole = max(128, 1 << (T - 1).bit_length())
    return min(block, whole), min(tile, whole)


def causal_attention(q, k, v, scale, *, window=None, scope="mla.attn",
                     out_dtype=F32, block=256, tile=256):
    """Causal softmax attention, only the score tiles a block of queries
    can see, by one of two paths, as the platform decides (:func:`on_tpu`;
    no option chooses):

    - on the TPU, the fused Pallas kernels of ``ops/flash_attention.py``,
      one a direction, at the block and tile :func:`kernel_tiles` picks
      from the head width: a block's scores, probabilities, running
      maximum and sum stay in VMEM, and the way back forms ``P`` and
      ``dS`` again from one float32 log-sum a row;
    - elsewhere (the CPU, where tier-1 runs), the tiled form below at
      ``block`` x ``tile``, which is also the kernel's parity oracle
      (``tests/test_attention_kernel.py``).

    Both visit the same pairs of a block of queries and a tile of keys:
    a block's tiles END at its own last row, so a tile wholly above the
    diagonal is never formed, forward, recomputed or on the way back;
    with a ``window`` (query ``i`` sees keys ``i - window < j <= i``)
    they also START at the first tile the block's window touches. In the
    tiled form the loop over key tiles takes its bounds from the outer
    loop's counter, and on the way back the pass over key tiles ends at
    the last block that can see the tile. A block and a tile are taken
    by ``dynamic_slice`` from the one copy of the keys and values: static
    slices of the keys to a band of blocks' past make a copy of them, and
    of their cotangents, a band (4.9 to 9.5 GB of temporaries at 4 to 16
    bands of the Ling cell's shapes, ``PERF.md`` Findings PR 38). The row
    maximum, row sum and unnormalised output are carried from tile to
    tile in float32; the way back is written by hand
    (:func:`_attend_back`).

    ``k`` and ``v`` may have fewer heads than ``q`` (a divisor: query
    head ``h`` reads key head ``h // group``). The ``group`` query heads
    of a key head meet that head's keys together (the tiled form lays
    them side by side as the ROWS of a block, ``group x block`` rows
    against one tile; the kernel takes them a head at a time inside one
    step): keys and values are never repeated in memory, and the keys'
    and values' gradients sum over the group.

    ``T`` at or under one block is one tile; otherwise ``T`` is padded
    to whole blocks and tiles (a padded key lies after every real
    query). ``q`` [B, T, H, D], ``k`` [B, T, H / group, D], ``v`` [B, T,
    H / group, Dv]; scores, mask, maximum, exponentials and sums
    float32, the probabilities in ``v``'s type for their product with
    the values, which accumulates in float32; the normalised output
    leaves in ``out_dtype`` (and is kept for the way back in it: at 72
    heads of 128 over 4 x 8192 positions a float32 output is 1.2 GB, in
    each of its two layouts). ``scope`` is the ``jax.named_scope`` the
    CALLER opens around this call: the hand-written ways back, which do
    not inherit it, set it by hand, and the trace-time record
    (:func:`score_tiles`) is kept by it."""
    B, T, H, D = q.shape
    if H % k.shape[2]:
        raise ValueError(f"{H} query heads over {k.shape[2]} key heads")
    group = H // k.shape[2]
    block = min(block, T)
    tile = min(tile, T + -T % block)
    if not on_tpu():
        _score_tiles[scope] = _tiles_record(T, block, tile, window, False)
        return _tiled(q, k, v, scale=scale, block=block, tile=tile,
                      window=window, group=group, scope=scope,
                      out_dtype=jnp.dtype(out_dtype))
    block, tile = kernel_tiles(T, D)
    _score_tiles[scope] = _tiles_record(T, block, tile, window, True)
    return flash_attention(q, k, v, scale, block=block, tile=tile,
                           window=window, scope=scope, out_dtype=out_dtype)


def _tiles_record(T, block, tile, window, kernel):
    T = padded(T, block, tile)
    return dict(computed=len(visits(T, block, tile, window)[0]),
                square=(T // block) * (T // tile), block=block, tile=tile,
                kernel=kernel)


def _tiled(q, k, v, *, scale, block, tile, window, group, scope,
           out_dtype):
    """The tiled form of :func:`causal_attention`."""
    T = q.shape[1]
    pad = padded(T, block, tile) - T
    rows = lambda a, group: _group_rows(
        jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))), group, block)
    o = _attend(rows(q, group), rows(k, 1), rows(v, 1), scale, block, tile,
                window, group, scope, out_dtype)
    return _ungroup_rows(o, group, block)[:, :T]


def _group_rows(a, group, block):
    """[B, T, H * group, D] -> [B, H, T * group, D]: a block of
    positions holds its ``group`` heads' rows one head after the other."""
    B, T, H, D = a.shape
    if group == 1:  # the same layout, and the transpose XLA has always had
        return a.swapaxes(1, 2)
    a = a.reshape(B, T // block, block, H // group, group, D)
    return a.transpose(0, 3, 1, 4, 2, 5).reshape(B, H // group, T * group, D)


def _ungroup_rows(a, group, block):
    B, H, rows, D = a.shape
    if group == 1:
        return a.swapaxes(1, 2)
    a = a.reshape(B, H, rows // (group * block), group, block, D)
    return a.transpose(0, 2, 4, 1, 3, 5).reshape(B, rows // group, H * group, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _attend(q, k, v, scale, block, tile, window, group, scope, out_dtype):
    """:func:`causal_attention` on ``[B, H, T, .]`` keys and values, ``T``
    whole blocks and tiles, and queries with their group as rows
    (:func:`_group_rows`)."""
    return _attend_kept(q, k, v, scale, block, tile, window, group, scope,
                        out_dtype)[0]


_cut = functools.partial(jax.lax.dynamic_slice_in_dim, axis=2)


def _tiles_met(i, block, tile):
    """One past the last key tile query block ``i`` meets: those that
    start at or before its last row. ``i`` a Python or a traced integer."""
    return -(-(i + 1) * block // tile)


def _first_tile(i, block, tile, window):
    """The first key tile the window of query block ``i`` touches."""
    if window is None:
        return 0
    first_key = i * block - window + 1
    most = max if isinstance(first_key, int) else jnp.maximum
    return most(first_key, 0) // tile


def _blocks_seeing(j, block, tile, window, blocks):
    """The query blocks that can see key tile ``j``: from the one that
    holds its first key to one past the one that holds the last query
    whose window reaches its last key."""
    first = j * tile // block
    if window is None:
        return first, blocks
    return first, jnp.minimum(((j + 1) * tile + window - 2) // block + 1,
                              blocks)


def _seen(i, j, block, tile, window, group):
    """The mask of block ``i``'s rows against tile ``j``, [group x block,
    tile]."""
    key = j * tile + jnp.arange(tile)[None, :]
    query = i * block + jnp.tile(jnp.arange(block), group)[:, None]
    if window is None:
        return key <= query
    return jnp.logical_and(key <= query, key > query - window)


def _attend_kept(q, k, v, scale, block, tile, window, group, scope,
                 out_dtype):
    B, H, T, _ = k.shape
    Dv = v.shape[-1]
    rows = group * block

    def over_keys(_, i):
        qi = _cut(q, i * rows, rows)

        def meet(j, carried):
            m, l, o = carried
            s = jnp.einsum("bhqd,bhkd->bhqk", qi, _cut(k, j * tile, tile),
                           preferred_element_type=F32) * scale
            s = jnp.where(_seen(i, j, block, tile, window, group), s,
                          -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # finite without a window (tile 0 holds key 0); with one, a
            # row whose own window starts past this tile has met no key
            at = m_new if window is None else jnp.where(
                m_new == -jnp.inf, 0.0, m_new)
            p = jnp.exp(s - at[..., None])
            shrink = jnp.exp(m - at)
            return (m_new, shrink * l + jnp.sum(p, axis=-1),
                    shrink[..., None] * o + jnp.einsum(
                        "bhqk,bhkd->bhqd", p.astype(v.dtype),
                        _cut(v, j * tile, tile), preferred_element_type=F32))

        m, l, o = jax.lax.fori_loop(
            _first_tile(i, block, tile, window), _tiles_met(i, block, tile),
            meet,
            (jnp.full((B, H, rows), -jnp.inf, F32),
             jnp.zeros((B, H, rows), F32), jnp.zeros((B, H, rows, Dv), F32)))
        return None, ((o / l[..., None]).astype(out_dtype), m + jnp.log(l))

    _, (o, logsum) = jax.lax.scan(over_keys, None, jnp.arange(T // block))
    # [blocks, B, H, rows, ..] -> [B, H, T x group, ..]
    whole = lambda a: jnp.moveaxis(a, 0, 2).reshape(
        (B, H, T * group) + a.shape[4:])
    o, logsum = whole(o), whole(logsum)
    return o, (q, k, v, o, logsum)


def _attend_back(scale, block, tile, window, group, scope, out_dtype, kept,
                 g):
    """From each row's log-sum alone: a tile's ``P = exp(S - logsum)``
    and ``dS = P (g V^T - rowsum(g o))`` are formed again, once for
    ``dQ += dS K`` a block of queries at a time and once for ``dK += dS^T
    Q``, ``dV += P^T g`` a tile of keys at a time, each loop over the
    tiles or blocks that see each other only. Two passes, so that every
    sum is carried by its own loop: one pass that adds ``dQ`` (or ``dK``
    and ``dV``) into a whole float32 array in place is a scatter under
    the nodes' ``vmap`` and took 1.6 times as long on the v5e
    (``PERF.md`` Findings PR 38). Products of ``v``'s and ``q``'s types
    into float32, as reverse mode through the forward products has
    them."""
    q, k, v, o, logsum = kept
    blocks = k.shape[2] // block
    rows = group * block
    mm = functools.partial(jnp.einsum, preferred_element_type=F32)
    # a hand-written way back does not inherit the name stack of the way
    # forward: the scope the device time is read by is set here
    with jax.named_scope(scope):
        drop = jnp.sum(g.astype(F32) * o, axis=-1)
        g = g.astype(v.dtype)

        def again(i, j):
            at = i * rows
            qi, gi = _cut(q, at, rows), _cut(g, at, rows)
            kj, vj = _cut(k, j * tile, tile), _cut(v, j * tile, tile)
            s = mm("bhqd,bhkd->bhqk", qi, kj) * scale
            p = jnp.where(_seen(i, j, block, tile, window, group),
                          jnp.exp(s - _cut(logsum, at, rows)[..., None]), 0.0)
            ds = p * (mm("bhqd,bhkd->bhqk", gi, vj)
                      - _cut(drop, at, rows)[..., None]) * scale
            return p.astype(v.dtype), ds.astype(q.dtype), qi, gi, kj

        def over_keys(_, i):
            def meet(j, dq):
                _, ds, _, _, kj = again(i, j)
                return dq + mm("bhqk,bhkd->bhqd", ds, kj)

            dq = jax.lax.fori_loop(
                _first_tile(i, block, tile, window),
                _tiles_met(i, block, tile), meet,
                jnp.zeros(q.shape[:2] + (rows, q.shape[3]), F32))
            return None, dq.astype(q.dtype)

        def over_queries(_, j):
            def meet(i, carried):
                p, ds, qi, gi, _ = again(i, j)
                return (carried[0] + mm("bhqk,bhqd->bhkd", ds, qi),
                        carried[1] + mm("bhqk,bhqd->bhkd", p, gi))

            dk, dv = jax.lax.fori_loop(
                *_blocks_seeing(j, block, tile, window, blocks), meet,
                (jnp.zeros(k.shape[:2] + (tile, k.shape[3]), F32),
                 jnp.zeros(v.shape[:2] + (tile, v.shape[3]), F32)))
            return None, (dk.astype(k.dtype), dv.astype(v.dtype))

        _, dq = jax.lax.scan(over_keys, None, jnp.arange(blocks))
        _, (dk, dv) = jax.lax.scan(over_queries, None,
                                   jnp.arange(k.shape[2] // tile))
        whole = lambda a, like: jnp.moveaxis(a, 0, 2).reshape(like.shape)
        return whole(dq, q), whole(dk, k), whole(dv, v)


_attend.defvjp(_attend_kept, _attend_back)


class MLAMixer(nn.Module):
    """Latent attention: ``q = x W_q``; ``[c | k_R] = x W_dkv``, ``c``
    normalised; ``[k_N | v] = c W_ukv``; ``k = [k_N | k_R]`` with ``k_R``
    every head's. Ling's form (the default) puts an RMSNorm over each head
    of q and of k, the interleaved rotary embedding on the last ``rope``
    dimensions of both, and one sigmoid output gate a head. With
    ``use_nope`` (Kimi Linear's ``mla_use_nope``) there is none of the
    three and their parameters are not made: the ``rope`` dimensions
    enter the score as plain dimensions."""

    heads: int
    nope: int = 128
    rope: int = 64
    v_dim: int = 128
    kv_rank: int = 512
    theta: float = 6e6
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_nope: bool = False

    @nn.compact
    def __call__(self, x):
        B, T, _ = x.shape
        H, N, R, Dv = self.heads, self.nope, self.rope, self.v_dim
        ling = not self.use_nope
        norm = lambda name, width: self.param(
            name, nn.initializers.ones, (width,), self.param_dtype)
        q = _dense(H * (N + R), "mla_q", self)(x).reshape(B, T, H, N + R)
        ckr = _dense(self.kv_rank + R, "mla_dkv", self)(x)
        c = rms_norm(ckr[..., :self.kv_rank], norm("c_norm", self.kv_rank),
                     self.eps).astype(self.dtype)
        kv = _dense(H * (N + Dv), "mla_ukv", self)(c).reshape(B, T, H, N + Dv)
        k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(
            ckr[:, :, None, self.kv_rank:], (B, T, H, R))], axis=-1)
        if ling:
            q = rms_norm(q, norm("q_norm", N + R), self.eps)
            k = rms_norm(k, norm("k_norm", N + R), self.eps)
        turn = lambda a: (jnp.concatenate(
            [a[..., :N], rope_interleaved(a[..., N:], self.theta)], axis=-1)
            if ling else a).astype(self.dtype)
        if ling:
            gate = jax.nn.sigmoid(_dense(H, "mla_g", self)(x).astype(F32))
        with jax.named_scope("mla.attn"):
            o = causal_attention(turn(q), turn(k), kv[..., N:],
                                 (N + R) ** -0.5,
                                 out_dtype=F32 if ling else self.dtype)
        if ling:
            o = o * gate[..., None]
        o = o.astype(self.dtype).reshape(B, T, H * Dv)
        return _dense(x.shape[-1], "mla_o", self)(o)


# --------------------------------------------------------------------------
# the expert layer


def route(x, frozen, *, n_group, topk_group, top_k, scale):
    """DeepSeek-V3's router: sigmoid scores over ALL experts
    (``frozen["router"]``), a selection bias (``frozen["bias"]``) added
    for the choice only, groups scored by the sum of their two largest,
    ``topk_group`` groups kept, the ``top_k`` largest among them chosen,
    weights ``scale * s_i / sum_chosen s_j``. float32 throughout.
    Returns the chosen ids and weights, [N, top_k]."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(F32), frozen["router"].astype(F32),
                               precision=HI))
    sel = s + frozen["bias"].astype(F32)
    n, e = sel.shape
    grouped = sel.reshape(n, n_group, e // n_group)
    top2 = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, best = jax.lax.top_k(top2, topk_group)
    keep = jnp.zeros((n, n_group), bool).at[
        jnp.arange(n)[:, None], best].set(True)
    sel = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(n, e)
    _, idx = jax.lax.top_k(sel, top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    return idx, scale * w / jnp.sum(w, axis=1, keepdims=True)


#: most rows of a block of sorted pairs: its float32 output is ``rows x
#: d`` (0.8 GB at 3072 wide), and twice the even share of 10 chosen of
#: 256 with 64 held would be 2.5 times as many
BLOCK_ROWS = 65536


def _dispatch(idx, n_experts, held, offset):
    """The chosen pairs sorted by held expert: ``order`` (pair ids, the
    pairs of absent experts last, padded to whole blocks), the held
    experts' ``counts`` and running ``ends``, and the static block shape:
    a block holds twice the pairs expected under even routing (a
    multiple of 128) and at most ``BLOCK_ROWS``, and the blocks cover
    every pair that can be chosen."""
    N, top_k = idx.shape
    local = idx - offset
    here = jnp.logical_and(local >= 0, local < held)
    pair_e = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(pair_e, stable=True)
    counts = jnp.bincount(pair_e, length=held + 1)[:held]
    most = N * min(top_k, held)
    rows = min(most, BLOCK_ROWS,
               -(-2 * N * top_k * held // n_experts // 128) * 128)
    n_blocks = -(-most // rows)
    order = jnp.pad(order, (0, max(n_blocks * rows - order.shape[0], 0)))
    return order, counts, jnp.cumsum(counts), rows, n_blocks


def _block_rows(x, first, order, counts, ends, *, rows, top_k, dtype):
    """Rows ``first .. first + rows`` of the sorted pairs: which are
    ``live``, their tokens ``tok``, the tokens' rows of ``x`` gathered
    (``xs``, in ``dtype``) and how many rows each held expert has in the
    block (``sizes``)."""
    with jax.named_scope("moe.dispatch"):
        # rows past the last chosen pair compute nothing and give and
        # take nothing (a grouped product leaves them unspecified)
        live = (first + jnp.arange(rows) < ends[-1])[:, None]
        tok = jax.lax.dynamic_slice(order, (first,), (rows,)) // top_k
        xs = jnp.where(live, x[tok], 0).astype(dtype)
        sizes = (jnp.clip(ends - first, 0, rows)
                 - jnp.clip(ends - counts - first, 0, rows)).astype(jnp.int32)
    return live, tok, xs, sizes


def _block(x, pair_w, first, order, counts, ends, w_gu, w_d, *, rows, top_k,
           dtype):
    """Rows ``first .. first + rows`` of the sorted pairs: gather their
    tokens, one grouped product a projection, weigh, scatter back.
    ``pair_w`` [rows]: the pairs' weights. Returns [N, d] float32."""
    live, tok, xs, sizes = _block_rows(x, first, order, counts, ends,
                                       rows=rows, top_k=top_k, dtype=dtype)
    with jax.named_scope("moe.experts"):
        h = swiglu(jax.lax.ragged_dot(
            xs, w_gu.astype(dtype), sizes,
            preferred_element_type=F32)).astype(dtype)
        ys = jax.lax.ragged_dot(h, w_d.astype(dtype), sizes,
                                preferred_element_type=F32)
    with jax.named_scope("moe.combine"):
        ys = jnp.where(live, ys * pair_w[:, None], 0.0)
        return jnp.zeros(x.shape, F32).at[tok].add(ys)


def _block_back(x, pair_w, g, first, order, counts, ends, w_gu, w_d, *, rows,
                top_k, dtype):
    """The way back of :func:`_block` from ``g`` [N, d] float32, the
    gradient of its output, to ``x`` and to ``pair_w``, in three grouped
    products. The up-projection is formed again; the down-projection is
    not: with ``G = g[tok]`` and ``ys = h W_d``, the pair weights'
    gradient ``rowsum(G * ys)`` is ``rowsum((G W_d^T) * h)``, and ``G
    W_d^T`` times the pair's weight is the gradient of ``h``. The third
    product takes the SwiGLU's gradient back through ``W_gu^T``. The
    transposed products are of a float32 gradient with the ``dtype``
    weights, and the rows' gradient is summed over a token's pairs in
    ``x``'s type, as reverse mode through ``_block`` has them. Returns
    ``dx`` [N, d] and ``dpw`` [rows] float32."""
    live, tok, xs, sizes = _block_rows(x, first, order, counts, ends,
                                       rows=rows, top_k=top_k, dtype=dtype)
    w_gu, w_d = w_gu.astype(dtype), w_d.astype(dtype)
    grouped = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                                preferred_element_type=F32)
    # a hand-written way back does not inherit the name stack of the way
    # forward: each part is under the scope of the part it undoes
    with jax.named_scope("moe.combine"):
        G = jnp.where(live, g[tok], 0.0)
    with jax.named_scope("moe.experts"):
        act, act_back = jax.vjp(swiglu, grouped(xs, w_gu))
        dh = grouped(G, jnp.swapaxes(w_d, 1, 2))
        dpw = jnp.sum(jnp.where(live, dh * act.astype(dtype), 0.0), axis=-1)
        dgu, = act_back(dh * pair_w[:, None])
        dxs = grouped(dgu, jnp.swapaxes(w_gu, 1, 2))
    with jax.named_scope("moe.dispatch"):
        dxs = jnp.where(live, dxs.astype(x.dtype), 0)
        return jnp.zeros(x.shape, x.dtype).at[tok].add(dxs), dpw


def held_experts(x, frozen, *, router, offset, dtype):
    """The held experts' part of the layer's output for ``x`` [N, d]
    and what the dispatch counted: ``y`` [N, d] and ``stats`` [2]
    (chosen pairs not computed, which has to be 0; the largest load of
    a held expert over their mean). ``router`` is the model's own:
    ``(x, frozen) -> (ids, weights)``, both [N, top_k], over all the
    ``frozen["router"].shape[1]`` experts (:func:`route` with its groups
    and bias for Ling); ``frozen["gate_up"]`` and ``frozen["down"]`` are
    the experts ``offset ..`` held here.

    The chosen (token, held expert) pairs are sorted by expert and run
    through one grouped product a projection, a block of rows at a time
    (``_dispatch``); a block past the last pair is skipped. Nothing is
    dropped, whatever the imbalance."""
    w_gu, w_d = frozen["gate_up"], frozen["down"]
    held, n_experts = w_gu.shape[0], frozen["router"].shape[1]
    with jax.named_scope("moe.route"):
        idx, w = router(x, frozen)
    with jax.named_scope("moe.dispatch"):
        order, counts, ends, rows, n_blocks = _dispatch(
            idx, n_experts, held, offset)
        pair_w = jnp.pad(w.reshape(-1), (0, order.shape[0] - w.size))[order]
    block = functools.partial(_block, rows=rows, top_k=idx.shape[1],
                              dtype=dtype)

    def one(y, first):
        return y + jax.lax.cond(
            first < ends[-1],
            lambda: block(x, jax.lax.dynamic_slice(pair_w, (first,), (rows,)),
                          first, order, counts, ends, w_gu, w_d),
            lambda: jnp.zeros(x.shape, F32)), None

    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, F32),
                        jnp.arange(n_blocks) * rows)
    total = ends[-1]
    computed = jnp.minimum(total, n_blocks * rows)
    load = jnp.max(counts) / jnp.maximum(jnp.mean(counts.astype(F32)), 1e-9)
    stats = jnp.stack([(total - computed).astype(F32), load.astype(F32)])
    return y.astype(dtype), stats


def held_experts_back(x, g, frozen, *, router, offset, dtype):
    """The gradient of ``held_experts``'s ``y`` to its rows, by
    recomputation: the forward pass kept nothing, the base has no
    gradient. Each block goes back where its up-projection is formed
    again (``_block_back``: three grouped products, to its rows and its
    pairs' weights), so that nothing is kept from block to block either;
    the weights' gradients then go back through the router's scores."""
    w_gu, w_d = frozen["gate_up"], frozen["down"]
    held, n_experts = w_gu.shape[0], frozen["router"].shape[1]
    g = g.astype(F32)
    with jax.named_scope("moe.route"):
        w, route_back, idx = jax.vjp(
            lambda x_: router(x_, frozen)[::-1], x, has_aux=True)
    with jax.named_scope("moe.dispatch"):
        order, counts, ends, rows, n_blocks = _dispatch(
            idx, n_experts, held, offset)
        pair_w = jnp.pad(w.reshape(-1), (0, order.shape[0] - w.size))[order]
    back = functools.partial(_block_back, rows=rows, top_k=idx.shape[1],
                             dtype=dtype)

    def one(carry, first):
        dx, dpw = carry

        def run():
            dx_b, dpw_b = back(
                x, jax.lax.dynamic_slice(pair_w, (first,), (rows,)), g,
                first, order, counts, ends, w_gu, w_d)
            return (dx + dx_b.astype(F32),
                    jax.lax.dynamic_update_slice(dpw, dpw_b, (first,)))

        return jax.lax.cond(first < ends[-1], run, lambda: (dx, dpw)), None

    (dx, dpw), _ = jax.lax.scan(
        one, (jnp.zeros(x.shape, F32), jnp.zeros(pair_w.shape, F32)),
        jnp.arange(n_blocks) * rows)
    with jax.named_scope("moe.route"):
        dw = jnp.zeros(pair_w.shape, F32).at[order].set(dpw)[:w.size]
        dx = dx + route_back(dw.reshape(w.shape))[0].astype(F32)
    return dx.astype(x.dtype)


def _frozen_experts(**static):
    """``held_experts`` as a frozen layer: rows of all nodes in one
    dispatch under ``vmap`` (``fold_rows``), the gradient to the rows
    only and by recomputation (the base has none and keeps nothing)."""
    fwd = fold_rows(lambda x, fr: held_experts(x, fr, **static),
                    lambda out: (True, False))
    bwd = fold_rows(
        lambda rows, fr: held_experts_back(rows[0], rows[1], fr, **static),
        lambda out: True)

    @jax.custom_vjp
    def layer(x, fr):
        return fwd(x, fr)

    layer.defvjp(lambda x, fr: (fwd(x, fr), (x, fr)),
                 lambda res, g: (bwd((res[0], g[0]), res[1]), None))
    return layer


class ExpertFFN(nn.Module):
    """The held experts' part of a routed layer plus its one shared
    expert of width ``shared_width``; ``shared_width`` 0 is a layer with
    NO shared expert: no shared projection is made and nothing is added.
    With ``shared_gate`` the shared expert's output is scaled by
    ``sigmoid(x w_sg)``, one number a token from a projection of its own.
    ``router`` is the model's own ``(x, frozen) -> (ids, weights)`` over
    ``frozen["router"]`` [d, n_experts]; without one it is :func:`route`
    over ``n_group`` groups, which also holds a seeded selection bias
    (``n_group`` 1 with ``topk_group`` 1 is the plain biased sigmoid
    top-k: the one group is always kept). With ``experts_held ==
    n_experts`` (and ``expert_offset`` 0) the layer holds every expert
    it routes over and its output is the whole layer's."""

    n_experts: int
    experts_held: int
    expert_offset: int
    width: int
    shared_width: int
    top_k: int
    n_group: int = 1
    topk_group: int = 1
    scale: float = 1.0
    router: Any = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    shared_gate: bool = False

    @nn.compact
    def __call__(self, x):
        B, T, d = x.shape
        he = nn.initializers.variance_scaling(1.0, "fan_in", "normal",
                                              in_axis=-2, out_axis=-1,
                                              batch_axis=(0,))
        frozen = {"router": self.param(
            "router", nn.initializers.lecun_normal(), (d, self.n_experts),
            self.param_dtype)}
        router = self.router
        if router is None:
            # the selection bias: frozen and seeded (trained models balance
            # their load with it; it takes no gradient)
            frozen["bias"] = self.param(
                "router_bias", nn.initializers.normal(0.01),
                (self.n_experts,), self.param_dtype)
            router = functools.partial(
                route, n_group=self.n_group, topk_group=self.topk_group,
                top_k=self.top_k, scale=self.scale)
        frozen["gate_up"] = self.param(
            "experts_gate_up", he, (self.experts_held, d, 2 * self.width),
            self.param_dtype)
        frozen["down"] = self.param(
            "experts_down", he, (self.experts_held, self.width, d),
            self.param_dtype)
        layer = _frozen_experts(router=router, offset=self.expert_offset,
                                dtype=self.dtype)
        y, stats = layer(x.reshape(B * T, d), frozen)
        if not self.shared_width:
            return y.reshape(B, T, d), stats
        with jax.named_scope("moe.shared"):
            shared = _dense(d, "shared_down", self)(swiglu(
                _dense(2 * self.shared_width, "shared_gate_up", self)(x)))
            if self.shared_gate:
                shared = (shared * jax.nn.sigmoid(_dense(
                    1, "shared_gate", self)(x).astype(F32))).astype(self.dtype)
        return y.reshape(B, T, d) + shared, stats


class DenseFFN(nn.Module):
    width: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        return _dense(x.shape[-1], "down", self)(swiglu(
            _dense(2 * self.width, "gate_up", self)(x)))


# --------------------------------------------------------------------------
# the model


class LingBlock(nn.Module):
    mixer: str  # "kda" | "mla"
    experts: bool
    cfg: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        kw = dict(dtype=c["dtype"], param_dtype=c["param_dtype"])
        h = RMSNorm(c["eps"], name="mixer_norm", **kw)(x)
        if self.mixer == "mla":
            x = x + MLAMixer(
                c["heads"], c["nope"], c["rope"], c["v_dim"], c["kv_rank"],
                c["theta"], c["eps"], name="mla", **kw)(h)
        else:
            x = x + KDAMixer(
                c["heads"], c["head_dim"], c["conv"], c["kda_lower_bound"],
                c["kda_chunk"], c["eps"], name="kda", **kw)(h)
        h = RMSNorm(c["eps"], name="ffn_norm", **kw)(x)
        if self.experts:
            y, stats = ExpertFFN(
                c["n_experts"], c["experts_held"], c["expert_offset"],
                c["expert_width"], c["shared_width"], c["top_k"],
                c["n_group"], c["topk_group"], c["route_scale"],
                name="moe", **kw)(h)
            return x + y, stats
        return x + DenseFFN(c["dense_width"], name="ffn", **kw)(h), None


class CausalLM(nn.Module):
    """Token ids [B, T] -> logits [B, T, vocab] (``__call__``: small
    sizes only), or with labels the mean next-token loss, head and loss a
    chunk of ``loss_chunk`` positions at a time (``loss``: a chunk's
    float32 logits are ``[B, loss_chunk, vocab]``, so a model with a
    wide vocabulary sets a small chunk). A model gives ``vocab``,
    ``hidden``, ``eps``, ``loss_chunk``, ``dtype`` and ``param_dtype``
    as fields and in its ``setup`` its ``blocks`` (each ``x -> (x,
    expert-layer counters or None)``) between :meth:`setup_ends`. With
    ``tie_head`` the logits are ``h E^T`` over the embedding's own
    matrix ``E`` and no ``head`` parameter is made: a frozen base then
    holds one matrix, used twice, seeded at a head's scale (``1 /
    sqrt(hidden)`` a row element: with an embedding's 1.0 the seed's
    logits would have a deviation of ``sqrt(hidden)`` and its loss
    would be a saturated softmax's). ``norm`` is the final norm's
    module, made as ``norm(eps, dtype=, param_dtype=)``."""

    tie_head: bool = False

    def setup_ends(self, norm=RMSNorm):
        self.embed = nn.Embed(
            self.vocab, self.hidden, dtype=self.dtype,
            param_dtype=self.param_dtype,
            embedding_init=nn.initializers.normal(
                self.hidden ** -0.5 if self.tie_head else 1.0))
        self.final_norm = norm(self.eps, dtype=self.dtype,
                               param_dtype=self.param_dtype)
        if not self.tie_head:
            self.head = self.param(
                "head", nn.initializers.lecun_normal(),
                (self.hidden, self.vocab), self.param_dtype)

    def hidden_states(self, tokens):
        x = self.embed(tokens.astype(jnp.int32))
        stats = []
        for blk in self.blocks:
            x, s = blk(x)
            if s is not None:
                stats.append(s)
        return self.final_norm(x), stats

    def _logits(self, h):
        if self.tie_head:
            return jnp.einsum("...d,vd->...v", h,
                              self.embed.embedding.astype(self.dtype),
                              preferred_element_type=F32)
        return jnp.dot(h, self.head.astype(self.dtype),
                       preferred_element_type=F32)

    def __call__(self, tokens):
        return self._logits(self.hidden_states(tokens)[0])

    def loss(self, tokens, labels, mask=None):
        """Mean over the kept rows of each row's mean cross-entropy over
        its positions, and the expert layers' counters
        ``{"moe.dropped_pairs", "moe.load_max_over_mean"}`` [expert
        layers]. ``[tokens, vocab]`` logits are never whole."""
        h, stats = self.hidden_states(tokens)
        T = h.shape[1]

        @jax.checkpoint
        def chunk_loss(hc, yc):
            logits = self._logits(hc)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
            return jnp.sum(lse - ll, axis=1)

        with jax.named_scope("lm.head_loss"):
            per_row = sum(
                chunk_loss(h[:, t:t + self.loss_chunk],
                           labels[:, t:t + self.loss_chunk].astype(jnp.int32))
                for t in range(0, T, self.loss_chunk)) / T
        m = jnp.ones(per_row.shape, F32) if mask is None else mask.astype(F32)
        loss = jnp.sum(per_row * m) / jnp.maximum(jnp.sum(m), 1.0)
        return loss, self.counters(stats)

    def counters(self, stats):
        """The counters by name [layers] from what the blocks returned
        (every one that is not None): here the expert layers' [2]."""
        if not stats:
            return {}
        stats = jnp.stack(stats)
        return {"moe.dropped_pairs": stats[:, 0],
                "moe.load_max_over_mean": stats[:, 1]}


class LingLM(CausalLM):
    """Ling-3.0-flash: ``layers`` published layers from ``first_layer``
    on, every size a keyword argument."""

    vocab: int = 64
    hidden: int = 32
    layers: int = 4  # layers kept, taken from ``first_layer`` on
    first_layer: int = 0  # index in the published stack of the first
    layer_group: int = 3  # every ``layer_group``-th layer is MLA
    first_dense: int = 1  # published layers below this have a dense FFN
    heads: int = 2
    head_dim: int = 16  # KDA's key and value width
    nope: int = 16
    rope: int = 8
    v_dim: int = 16
    kv_rank: int = 12
    theta: float = 6e6
    conv: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk: int = 64
    dense_width: int = 48
    n_experts: int = 16
    experts_held: int = 4
    expert_offset: int = 0
    expert_width: int = 8
    shared_width: int = 8
    top_k: int = 4
    n_group: int = 4
    topk_group: int = 2
    route_scale: float = 2.5
    eps: float = 1e-6
    loss_chunk: int = 1024
    remat: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def setup(self):
        cfg = {f: getattr(self, f) for f in (
            "heads", "head_dim", "nope", "rope", "v_dim", "kv_rank", "theta",
            "conv", "kda_lower_bound", "kda_chunk", "dense_width", "n_experts",
            "experts_held", "expert_offset", "expert_width", "shared_width",
            "top_k", "n_group", "topk_group", "route_scale", "eps", "dtype",
            "param_dtype")}
        block = nn.remat(LingBlock) if self.remat else LingBlock
        self.setup_ends()
        self.blocks = [
            block("mla" if (i + 1) % self.layer_group == 0 else "kda",
                  i >= self.first_dense, cfg, name=f"layer_{i}")
            for i in range(self.first_layer, self.first_layer + self.layers)]


@register_model("ling-3.0-flash", "ling")
def _ling(num_classes: int | None = None, **kw) -> LingLM:
    del num_classes  # the vocabulary is the model's own
    return LingLM(**kw)


# adapters ride on the mixers' projections: KDA's q, k, v, o and MLA's
# q, kv-down, kv-up, o; every kernel is a plain [d_in, d_out]
register_lora_targets(
    "ling-3.0-flash", "ling",
    default=("kda_q", "kda_k", "kda_v", "kda_o",
             "mla_q", "mla_dkv", "mla_ukv", "mla_o"),
)
