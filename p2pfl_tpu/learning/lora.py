"""Adapter-only federation (LoRA): the unit of federation becomes the
adapter delta instead of the full parameter tree.

The scenario users actually want from a TPU-native FL framework is
collaborative fine-tuning of a pretrained transformer without shipping
full weights (ROADMAP item 3, Gemma-on-TPU in PAPERS.md). The perf
lever is structural, not numeric: once the unit of federation is the
adapter tree, every downstream consumer shrinks by the same orders of
magnitude *without changing*, because each is generic over "params":

- the socket wire envelope (``core.serialize.encode_parameters``), the
  v2 bf16/int8 dtypes and the int8 error-feedback state;
- the SPMD FedAvg contraction (``[n,n] @ [n,d']`` instead of ``[n,n] @
  [n,d]``) and the staged-overlap double buffer;
- the Krum/trimmed-mean flatten — the ``[n,n]`` Gram matmul drops from
  full-model ``d`` to adapter ``d'``;
- reputation cosine scoring (``entry_scales`` over adapter vectors) and
  the attack transforms (a malicious node poisons the adapters it
  ships, exactly as it poisoned full weights);
- checkpoints and the live-join STATE_SYNC payload.

Mechanically this is ONE seam: :class:`LoraModel` duck-types the two
methods ``make_step_fns`` uses (``init(rng, x)`` / ``apply(params,
x)``), returning and consuming an **adapter-only pytree**. The frozen
base never enters ``TrainState``, the optimizer state, the donated
``FederatedState`` buffers, or any wire payload. It is held ONCE on the
device and is an ARGUMENT of the compiled round and evaluation programs
(:func:`frozen_argument`; ``Scenario`` passes ``model.base``): a
constant of 5.6 GB would go into the HLO, the compile and every
compile-cache entry. Code that calls ``apply`` outside such a wrapper
(tests, the socket plane's small models) still gets the base the model
was built with.

The adapter is a SIDE PATH at each target layer,

    ``y = x W + (alpha / rank) * (x A) B``

(``nn.intercept_methods`` on the target ``Dense``/``DenseGeneral``; the
adapters ride in a flax collection ``"lora"`` beside ``"params"``, so a
model scanned over its layers hands each layer its own pair). The base
kernel is never rewritten: merging ``W + A B`` under the round's
``vmap`` over nodes would make n full copies of every target kernel a
step. :meth:`LoraModel.materialize` still gives the merged weights, for
export and for the tests. With ``A ~ N(0, 1/d_in)`` and ``B = 0`` the
side path adds exactly 0 at adapter init, so the wrapped model equals
the base **bit-exactly** — the property the cross-plane parity tests
anchor on.

Shape handling: a target kernel is viewed as ``lead axes + [d_in axes]
+ [d_out axes]``. ``lead`` (e.g. the ``nn.scan`` depth axis) broadcasts
— each scanned layer gets its own ``A``/``B`` pair via one batched
matmul. The per-target ``(out_axes, base_ndim)`` split is model
metadata registered next to the model factory
(``models.base.register_lora_targets``); anything unregistered falls
back to the plain 2-D view ``(..., d_in, d_out)``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from p2pfl_tpu.models.base import default_lora_targets, lora_axis_specs

# the combined-tree keys ``split_adapters``/``merge_adapters`` pivot on
BASE_KEY = "base"
ADAPTERS_KEY = "adapters"

# joins a tree path into the flat adapter-tree key; "/" cannot appear
# in flax module/param names
_SEP = "/"
# the flax collection the adapters ride in, beside "params"
LORA = "lora"


@dataclasses.dataclass(frozen=True)
class AdapterSite:
    """One target kernel: where it lives and its factorization view."""

    key: str  # _SEP-joined path, the adapter tree's dict key
    shape: tuple[int, ...]  # full kernel shape
    lead: tuple[int, ...]  # broadcast axes (scan depth, ...)
    d_in: int
    d_out: int


def _path_keys(path) -> tuple[str, ...]:
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def find_adapter_sites(
    params: Any, targets: tuple[str, ...],
    specs: dict[str, tuple[int, int]] | None = None,
) -> tuple[AdapterSite, ...]:
    """Resolve target patterns against a param tree.

    A leaf qualifies when its final path key is ``"kernel"`` and any
    path component contains a target pattern as a substring. Every
    pattern must match at least one kernel — a typo'd target silently
    adapting nothing would report a fine-tune that never ran, so this
    fails loud naming the tree's kernels (the ``check_parameters``
    leaf-naming convention).
    """
    if not targets:
        raise ValueError("lora targets must not be empty")
    specs = specs or {}
    sites: list[AdapterSite] = []
    matched: set[str] = set()
    kernels: list[str] = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = _path_keys(path)
        if not keys or keys[-1] != "kernel":
            continue
        key = _SEP.join(keys)
        kernels.append(key)
        hits = [t for t in targets if any(t in k for k in keys[:-1])]
        if not hits:
            continue
        matched.update(hits)
        out_axes, base_ndim = specs.get(hits[0], (1, 2))
        shape = tuple(leaf.shape)
        n_lead = leaf.ndim - base_ndim
        if n_lead < 0 or out_axes >= base_ndim:
            raise ValueError(
                f"lora target {hits[0]!r} spec (out_axes={out_axes}, "
                f"base_ndim={base_ndim}) does not fit kernel {key} "
                f"of shape {shape}"
            )
        lead = shape[:n_lead]
        d_in = math.prod(shape[n_lead:leaf.ndim - out_axes])
        d_out = math.prod(shape[leaf.ndim - out_axes:])
        sites.append(AdapterSite(key=key, shape=shape, lead=lead,
                                 d_in=d_in, d_out=d_out))
    missing = [t for t in targets if t not in matched]
    if missing:
        raise ValueError(
            f"lora targets {missing} match no kernel; tree has "
            f"{kernels}"
        )
    return tuple(sites)


def init_adapters(sites: tuple[AdapterSite, ...], rank: int,
                  rng: jax.Array) -> dict:
    """Fresh A/B leaves per site: ``A ~ N(0, 1/d_in)``, ``B = 0`` — the
    zero-init that makes ``merged == base`` bit-exact at start."""
    if rank < 1:
        raise ValueError(f"lora rank must be >= 1, got {rank}")
    adapters: dict[str, dict[str, jax.Array]] = {}
    for i, site in enumerate(sites):
        a_rng = jax.random.fold_in(rng, i)
        a = jax.random.normal(
            a_rng, site.lead + (site.d_in, rank), jnp.float32
        ) * (1.0 / math.sqrt(site.d_in))
        b = jnp.zeros(site.lead + (rank, site.d_out), jnp.float32)
        adapters[site.key] = {"A": a, "B": b}
    return adapters


def lora_scale(rank: int, alpha: float | None) -> float:
    """``alpha / rank``; ``alpha`` unset means ``rank`` (scale 1)."""
    return (alpha if alpha is not None else float(rank)) / float(rank)


def adapter_deltas(adapters: dict, sites: tuple[AdapterSite, ...],
                   rank: int, alpha: float | None) -> dict:
    """``(alpha/rank) * A @ B`` per site, reshaped to the kernel shape.
    The matmul broadcasts over the lead axes, so scanned layers keep
    per-depth adapters in one contraction."""
    scale = lora_scale(rank, alpha)
    out = {}
    for site in sites:
        ab = adapters[site.key]
        delta = jnp.matmul(ab["A"], ab["B"]) * jnp.float32(scale)
        out[site.key] = delta.reshape(site.shape)
    return out


def split_adapters(tree: dict) -> tuple[Any, dict]:
    """``{"base": ..., "adapters": ...} -> (base, adapters)`` — the
    pure structural split of one lora tree. Inverse of
    :func:`merge_adapters`; round-trips bit-exactly by construction."""
    try:
        return tree[BASE_KEY], tree[ADAPTERS_KEY]
    except (KeyError, TypeError) as e:
        raise ValueError(
            f"not a lora tree: expected dict with {BASE_KEY!r}/"
            f"{ADAPTERS_KEY!r} keys, got {type(tree).__name__}"
        ) from e


def merge_adapters(base: Any, adapters: dict) -> dict:
    """``(base, adapters) -> {"base": ..., "adapters": ...}`` — the
    inverse of :func:`split_adapters` (no materialization; use
    :meth:`LoraModel.materialize` for the effective full weights)."""
    return {BASE_KEY: base, ADAPTERS_KEY: adapters}


def lora_init(params: Any, rank: int, targets: tuple[str, ...],
              *, alpha: float | None = None,
              rng: jax.Array | None = None,
              specs: dict[str, tuple[int, int]] | None = None) -> dict:
    """Build the frozen-base + adapter split for an existing param tree:
    one combined pytree ``{"base": params, "adapters": {site: {A, B}}}``
    (take it apart with :func:`split_adapters`)."""
    sites = find_adapter_sites(params, tuple(targets), specs)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    return merge_adapters(params, init_adapters(sites, rank, rng))


class LoraModel:
    """Adapter-only view of a flax model.

    Duck-types the surface ``make_step_fns`` consumes: ``init`` returns
    the adapter-only pytree (so ``TrainState.params`` and the optimizer
    state are adapter-sized), ``apply`` runs the frozen base with each
    adapter as a side path of its target layer. The base is held once,
    shared by all nodes of a federation — never donated, vmapped,
    shipped or optimized — and handed to the compiled programs as an
    argument (:func:`frozen_argument`, which binds it for the trace
    through :meth:`bound`).
    """

    def __init__(self, model, base: Any, rank: int,
                 targets: tuple[str, ...], alpha: float | None = None,
                 specs: dict[str, tuple[int, int]] | None = None):
        self.inner = model
        self.rank = int(rank)
        self.alpha = alpha
        self.targets = tuple(targets)
        self.base = jax.tree.map(jnp.asarray, base)
        self._bound = None
        self.sites = find_adapter_sites(self.base, self.targets, specs)
        if self.rank < 1:
            raise ValueError(f"lora rank must be >= 1, got {rank}")

    # -- the make_step_fns surface ------------------------------------
    def init(self, rng, sample_x) -> dict:
        del sample_x  # base already fixes every shape
        return init_adapters(self.sites, self.rank, rng)

    def apply(self, adapters: dict, x, *args, **kwargs):
        base = self.base if self._bound is None else self._bound
        side = functools.partial(_side_path,
                                 lora_scale(self.rank, self.alpha))
        with nn.intercept_methods(side):
            return self.inner.apply({**base, LORA: _nested(adapters)},
                                    x, *args, **kwargs)

    @contextlib.contextmanager
    def bound(self, base):
        """While a program is traced: ``apply`` reads ``base`` (the
        program's argument) in place of the arrays the model holds."""
        self._bound, was = base, self._bound
        try:
            yield
        finally:
            self._bound = was

    # -- merge math ----------------------------------------------------
    def materialize(self, adapters: dict) -> Any:
        """Effective full weights: ``base + (alpha/rank) * A @ B`` at
        every site, untouched leaves passed through by reference. For
        export and for tests; ``apply`` never merges."""
        deltas = adapter_deltas(adapters, self.sites, self.rank,
                                self.alpha)

        def leaf(path, w):
            d = deltas.get(_SEP.join(_path_keys(path)))
            return w if d is None else (w + d.astype(w.dtype))

        return jax.tree_util.tree_map_with_path(leaf, self.base)

    def adapter_param_count(self) -> int:
        return sum(
            math.prod(s.lead) * self.rank * (s.d_in + s.d_out)
            for s in self.sites
        )


def _nested(adapters: dict) -> dict:
    """``{"params/a/b/kernel": {A, B}} -> {"a": {"b": {A, B}}}``: the
    adapters as a flax collection, each pair at its layer's own path."""
    out: dict = {}
    for key, pair in adapters.items():
        *path, leaf = key.split(_SEP)[1:-1]
        at = out
        for k in path:
            at = at.setdefault(k, {})
        at[leaf] = pair
    return out


def _side_path(scale, next_fun, args, kwargs, context):
    """``y + scale * (x A) B`` at a layer that holds an adapter pair.
    ``x``'s trailing axes that the kernel contracts are laid flat
    (``d_in``), the low-rank product is computed in the layer's own
    compute type and shaped like ``y``."""
    mod = context.module
    y = next_fun(*args, **kwargs)
    if context.method_name != "__call__" or not (
            isinstance(mod, (nn.Dense, nn.DenseGeneral))
            and mod.has_variable(LORA, "A")):
        return y
    a, b = mod.get_variable(LORA, "A"), mod.get_variable(LORA, "B")
    x = args[0]
    lead = x.ndim
    while math.prod(x.shape[lead:]) < a.shape[0]:
        lead -= 1
    dt = getattr(mod, "dtype", None) or y.dtype
    with jax.named_scope("lora.side"):
        xa = jnp.dot(x.reshape(x.shape[:lead] + (-1,)).astype(dt),
                     a.astype(dt))
        return y + (jnp.dot(xa, b.astype(dt)) * jnp.asarray(scale, dt)
                    ).reshape(y.shape)


def frozen_args(model) -> tuple:
    """What a program compiled through :func:`frozen_argument` takes
    after its own arguments: ``(model.base,)`` for a :class:`LoraModel`,
    nothing for any other model."""
    return (model.base,) if hasattr(model, "bound") else ()


def frozen_argument(model, fn):
    """``fn(*args)`` as ``call(*args, frozen)``: the model's frozen tree
    is the LAST argument of the program that ``jax.jit`` compiles from
    ``call``, not a constant in it. A model with no frozen part gets
    ``fn`` back as it is."""
    if not hasattr(model, "bound"):
        return fn

    @functools.wraps(fn)
    def call(*args):
        *args, frozen = args
        with model.bound(frozen):
            return fn(*args)

    return call


def base_params_for(model, seed: int, sample_x) -> Any:
    """The frozen base every plane derives identically from config:
    ``model.init(PRNGKey(seed), sample)`` — the SAME key the full-weight
    paths use (``init_federation`` with ``same_init`` and
    ``JaxLearner.init``), so a lora federation's merged round-0 model
    equals the full-weight federation's round-0 model bit-exactly.
    Depends only on the sample's shape/dtype, never its values, so
    every node of a socket federation converges on one base. One
    compiled call: every leaf is made on the device in the model's own
    parameter type, and the initializer's forward pass is never run (a
    2.8B-parameter bfloat16 base has no float32 copy)."""
    return jax.jit(model.init)(jax.random.PRNGKey(seed),
                               jnp.asarray(sample_x))


def wrap_model(model, model_name: str, rank: int, *,
               targets: tuple[str, ...] = (), alpha: float | None = None,
               base: Any = None, seed: int = 0,
               sample_x=None) -> LoraModel:
    """Build a :class:`LoraModel` from registry metadata: empty
    ``targets`` resolve to the model's registered defaults, axis specs
    come from the same registry, and a missing ``base`` is derived
    deterministically via :func:`base_params_for`."""
    targets = tuple(targets) or default_lora_targets(model_name)
    specs = lora_axis_specs(model_name)
    if base is None:
        if sample_x is None:
            raise ValueError("wrap_model needs base= or sample_x=")
        base = base_params_for(model, seed, sample_x)
    return LoraModel(model, base, rank=rank, targets=targets,
                     alpha=alpha, specs=specs)


def maybe_wrap_lora(model, cfg, sample_x):
    """Scenario/launch seam: the model unchanged when ``cfg.lora`` is
    off, else the :class:`LoraModel` every plane must train through.
    Deterministic in ``(cfg.model, cfg.lora, cfg.seed)`` so separate
    node processes derive one identical frozen base."""
    if not cfg.lora.active:
        return model
    return wrap_model(
        model, cfg.model.model, cfg.lora.rank,
        targets=tuple(cfg.lora.targets), alpha=cfg.lora.alpha,
        seed=cfg.seed, sample_x=sample_x,
    )
