"""The delta rule's diagonal sub-blocks as one Pallas TPU kernel a
direction, the pairwise decays formed once a sub-chunk in VMEM.

The kernels compute what ``models/ling.py`` ``_diag_pairs_fwd`` and
``_diag_pairs_back`` compute for the form of ``kda_heads`` that is exact
for an unbounded decay: for each sub-chunk of ``sub`` rows of a chunk,
``[sub (i), sub (j)]`` of ``sum_c k_ic k_jc e^(G_ic - G_jc)`` and of
``sum_c q_ic k_jc e^(G_ic - G_jc)`` for ``j <= i``, 0 above the diagonal,
and on the way back the gradients to ``q``, ``k`` and ``G`` from those of
the two blocks. Nothing of size ``[.., sub, sub, K]`` reaches HBM.

A grid step takes :data:`LANES` (chunk, batch, head) triples, reads their
``q``, ``k`` and ``G`` once and turns each chunk row in VMEM so that the
triples lie along the lanes and the channels along the sublanes (``[C,
K, triples]``). There it forms each row's decay over its step, ``a_t =
e^(G_t - G_(t-1))``, once. A pair ``(i, j < i)`` of a sub-chunk takes its
factor as the product ``e^(G_i - G_j) = a_i a_(i-1) .. a_(j+1)``, built
key by key from ``j = i`` down: every factor at most 1, so it is exact
for any finite decay, as the difference's exponential is, with one
exponential a row and channel where the difference form takes one a
pair. Every pair is then one elementwise pass over the channels, 8 at a
time: the forward's two sums over the channels are vector adds and one
sum over 8 sublanes; the way back's five sums over rows and keys are
vector adds into the turned gradients. The upper triangle is never
visited and no sum runs across the lanes. The blocks and gradients are
turned back in VMEM on the way out.

Types as the XLA form has them: ``q``, ``k``, ``G`` float32, the
factors, products and sums float32. The operands are read as ``kda_heads``
stages them, chunk-first ``[.., C, K]``, as ``[triples, C, K]``: no copy
under the nodes' ``vmap`` (the way back), where XLA stores them so; on
the folded forward XLA stores them chunk-major (``[C, B, H, NC, K]``) and
copies them for the kernel (``PERF.md`` section 7, "After PR 46"). The two
blocks leave side by side in the lanes of one array, ``[.., C / sub, sub,
128]`` (``a`` in lanes ``0 .. sub``, ``b`` in ``sub .. 2 sub``), so that
whole rows of lanes go to HBM; the way back takes its two gradients the
same way. A count of triples that is no multiple of :data:`LANES` is
padded (not at the Kimi Linear cell's shapes).

``interpret=True`` runs the same kernels through Pallas's interpreter on
any backend: the CPU parity tests use it. In the program the kernels run
on the TPU only; ``kda_heads`` chooses them by platform.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: float32 sublanes and lanes of a vector register; the triples a grid
#: step takes are one lane each
ROWS, LANES = 8, 128
#: VMEM a kernel may use (the v5e has 128 MiB; Mosaic's default is 16)
VMEM_LIMIT = 96 * 2**20


def _turn_in(q_ref, k_ref, g_ref, qT, kT, aT):
    """``[triples, C, K]`` -> ``[C, K, triples]`` for ``q`` and ``k``, and
    each row's decay over its step, ``aT[t] = e^(G_t - G_(t-1))`` (row 0's
    is never read)."""
    def row(t, before):
        qT[t] = q_ref[:, t, :].T
        kT[t] = k_ref[:, t, :].T
        G = g_ref[:, t, :].T
        aT[t] = jnp.exp(G - before)
        return G

    jax.lax.fori_loop(0, q_ref.shape[1], row,
                      jnp.zeros((q_ref.shape[2], LANES), F32))


def _factors(aT, s, sub, i, c):
    """``[(j, e^(G_i - G_j))]`` over the channel group ``c`` for the keys
    ``j = i, i - 1, .., 0`` of sub-chunk ``s``: each the one before times
    the decay of the step it adds."""
    e = None
    for j in range(i, -1, -1):
        if j < i:
            a = aT[s * sub + j + 1, c]
            e = a if e is None else e * a
        yield j, e


def _store(ref, at, x):
    """``ref[at] = x``: a turned gradient's sum in VMEM, from the body of
    a loop over channel groups."""
    ref[at] = x


def _fwd_kernel(q_ref, k_ref, g_ref, o_ref, qT, kT, aT, AT, BT, *, sub):
    C, K = q_ref.shape[1:]
    _turn_in(q_ref, k_ref, g_ref, qT, kT, aT)
    AT[...] = jnp.zeros(AT.shape, F32)
    BT[...] = jnp.zeros(BT.shape, F32)

    def sub_chunk(s, _):
        for i in range(sub):
            def channels(g, sums):
                c = pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS)
                ki, qi = kT[s * sub + i, c], qT[s * sub + i, c]
                out = list(sums)
                for j, e in _factors(aT, s, sub, i, c):
                    ke = kT[s * sub + j, c]
                    if e is not None:
                        ke = ke * e
                    out[2 * j] = sums[2 * j] + ki * ke
                    out[2 * j + 1] = sums[2 * j + 1] + qi * ke
                return tuple(out)

            sums = jax.lax.fori_loop(
                0, K // ROWS, channels,
                (jnp.zeros((ROWS, LANES), F32),) * (2 * (i + 1)))
            for j in range(i + 1):
                at = pl.ds(i * sub + j, 1)
                AT[s, at] = jnp.sum(sums[2 * j], axis=0, keepdims=True)
                BT[s, at] = jnp.sum(sums[2 * j + 1], axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, C // sub, sub_chunk, 0)
    # [s, (i, j), triples] -> [triples, s, i, (a's j, b's j, 0 ..)], LANES /
    # sub rows at a time
    rows = LANES // sub
    zeros = jnp.zeros((LANES, LANES - 2 * sub), F32)
    for s in range(C // sub):
        for i0 in range(0, sub, rows):
            va = AT[s, pl.ds(i0 * sub, LANES)].T
            vb = BT[s, pl.ds(i0 * sub, LANES)].T
            for i in range(rows):
                cut = slice(i * sub, (i + 1) * sub)
                o_ref[:, s, i0 + i, :] = jnp.concatenate(  # fedlint: disable=recompile-hazard
                    [va[:, cut], vb[:, cut], zeros], axis=1)


def _bwd_kernel(q_ref, k_ref, g_ref, d_ref, dq_ref, dk_ref, dg_ref,
                qT, kT, aT, daT, dbT, dqT, dkT, dgT, *, sub):
    C, K = q_ref.shape[1:]
    _turn_in(q_ref, k_ref, g_ref, qT, kT, aT)
    # [triples, s, i, (da's j, db's j, ..)] -> [s, (i, j), triples], LANES /
    # sub rows at a time
    rows = LANES // sub
    for s in range(C // sub):
        for i0 in range(0, sub, rows):
            ds = [d_ref[:, s, i0 + i, :] for i in range(rows)]
            for lo, turned in ((0, daT), (sub, dbT)):
                v = jnp.concatenate(  # fedlint: disable=recompile-hazard
                    [d[:, lo:lo + sub] for d in ds], axis=1)
                turned[s, pl.ds(i0 * sub, LANES)] = v.T
    for turned in (dqT, dkT, dgT):
        turned[...] = jnp.zeros(turned.shape, F32)

    def sub_chunk(s, _):
        for i in range(sub):
            ri = s * sub + i

            def channels(g, _):
                c = pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS)
                ki, qi = kT[ri, c], qT[ri, c]
                dq = dk = dG = 0.0
                for j, e in _factors(aT, s, sub, i, c):
                    rj = s * sub + j
                    kj = kT[rj, c]
                    da, db = (jnp.broadcast_to(d[s, pl.ds(i * sub + j, 1)],
                                               (ROWS, LANES))
                              for d in (daT, dbT))
                    ke = kj if e is None else kj * e
                    dq = dq + db * ke
                    dk = dk + da * ke
                    # the factor k_j e_ij's gradient, to k_j and through
                    # e_ij to G_i and G_j (which cancel where j = i)
                    w = da * ki + db * qi
                    if e is not None:
                        w = w * e
                    _store(dkT, (rj, c), dkT[rj, c] + w)
                    if j < i:
                        t = w * kj
                        dG = dG + t
                        _store(dgT, (rj, c), dgT[rj, c] - t)
                _store(dqT, (ri, c), dqT[ri, c] + dq)
                _store(dkT, (ri, c), dkT[ri, c] + dk)
                if i:
                    _store(dgT, (ri, c), dgT[ri, c] + dG)
                return 0

            jax.lax.fori_loop(0, K // ROWS, channels, 0)
        return 0

    jax.lax.fori_loop(0, C // sub, sub_chunk, 0)

    def row(t, _):
        for ref, turned in ((dq_ref, dqT), (dk_ref, dkT), (dg_ref, dgT)):
            ref[:, t, :] = turned[t].T
        return 0

    jax.lax.fori_loop(0, C, row, 0)


def _call(kernel, name, ins, outs, scratch, interpret):
    """``pallas_call`` over ``[N, ..]`` operands, :data:`LANES` of the
    leading axis a step, every other axis whole; float32 VMEM
    ``scratch``."""
    spec = lambda shape: pl.BlockSpec(
        (LANES,) + shape[1:], lambda p: (p,) + (0,) * (len(shape) - 1))
    return pl.pallas_call(
        kernel,
        grid=(ins[0].shape[0] // LANES,),
        in_specs=[spec(a.shape) for a in ins],
        out_specs=[spec(s.shape) for s in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM(s, F32) for s in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
        name=name, interpret=interpret,
    )(*ins)


def _triples(x, lead):
    """``[*lead, ..]`` -> ``[N, ..]``, ``N`` padded to whole steps."""
    x = x.reshape((math.prod(lead),) + x.shape[len(lead):])
    pad = -x.shape[0] % LANES
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x


def _check(q, sub):
    C, K = q.shape[-2:]
    if sub % ROWS or C % sub or K % ROWS or LANES % (2 * sub):
        raise ValueError(
            f"sub-chunks of {sub} rows over chunks of {C} of {K} channels: "
            f"the sub-chunk has to divide the chunk, twice it {LANES}, and "
            f"it and the channels have to be whole tiles of {ROWS}")
    return q.shape[:-2], C, K


@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def forward(q, k, G, *, sub, interpret=False):
    """``(a, b)`` [.., C / sub, sub, sub] of ``q, k, G`` [.., C, K]
    float32: ``_diag_pairs_fwd``'s blocks."""
    lead, C, K = _check(q, sub)
    ins = [_triples(x, lead) for x in (q, k, G)]
    out = jax.ShapeDtypeStruct((ins[0].shape[0], C // sub, sub, LANES), F32)
    o, = _call(functools.partial(_fwd_kernel, sub=sub), "kda_diag_fwd",
               ins, [out],
               [(C, K, LANES)] * 3 + [(C // sub, sub * sub, LANES)] * 2,
               interpret)
    o = o[:math.prod(lead)].reshape(lead + o.shape[1:])
    return o[..., :sub], o[..., sub:2 * sub]


@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def backward(q, k, G, da, db, *, sub, interpret=False):
    """``(dq, dk, dG)`` [.., C, K] from the gradients ``da, db`` [.., C /
    sub, sub, sub] of the two blocks: ``_diag_pairs_back``'s."""
    lead, C, K = _check(q, sub)
    d = jnp.concatenate([da, db], axis=-1)
    d = jnp.pad(d, ((0, 0),) * (d.ndim - 1) + ((0, LANES - 2 * sub),))
    ins = [_triples(x, lead) for x in (q, k, G, d)]
    out = jax.ShapeDtypeStruct(ins[0].shape, F32)
    grads = _call(functools.partial(_bwd_kernel, sub=sub), "kda_diag_bwd",
                  ins, [out] * 3,
                  [(C, K, LANES)] * 3 + [(C // sub, sub * sub, LANES)] * 2
                  + [(C, K, LANES)] * 3, interpret)
    return tuple(g[:math.prod(lead)].reshape(q.shape) for g in grads)
