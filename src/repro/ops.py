"""repro.ops — the stable, transform-native public API (DESIGN.md §11).

This is the namespace models, pipelines and downstream PRs program against:
declarative hashable bucket specs plus the multisplit operator family, with
JAX transforms wired in as first-class citizens rather than afterthoughts:

* ``jit``  — specs are value-hashable, leafless pytrees, so equal spec
  instances share ONE trace (zero retraces across ``delta_buckets(32)``
  calls, whether the spec rides as a static argument or a pytree argument).
* ``vmap`` — :func:`multisplit` carries a ``jax.custom_batching.custom_vmap``
  rule that routes ``jax.vmap(ops.multisplit)`` onto a BATCHED plan
  (DESIGN.md §9): ONE kernel launch for the whole batch, bitwise equal to
  the per-row loop it replaces.  Without the rule, vmap would silently
  trace the flat pipeline per element and miss the batched layout.
* ``grad`` — :func:`multisplit_key_value` is a ``jax.custom_vjp``: the
  backward pass of the value permutation is the INVERSE GATHER of the
  forward permutation (one ``take`` — no scatter transpose, no dense
  one-hot), so routing/bucketing sits inside ``grad`` end-to-end.

Execution is unchanged underneath: every op resolves a
:class:`~repro.core.pipeline.MultisplitPlan` through the backend registry.
An op that names no ``backend`` runs where
:func:`~repro.core.pipeline.default_backend` sends it: the compiled
``pallas`` kernels on a TPU for 32-bit keys, ``vmap`` otherwise.
Ops are cached per (spec, shape, config) — hashable specs make the cache
exact, not identity-based.

Stability policy: everything in ``__all__`` is covered by the API snapshot
test (``tests/test_api_surface.py``); changing a signature here is a
deliberate, test-visible act.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import custom_batching

from repro.core.identifiers import (
    BitfieldSpec,
    BucketSpec,
    CallableSpec,
    DeltaSpec,
    EvenSpec,
    IdentitySpec,
    RangeSpec,
    as_spec,
    delta_buckets,
    even_buckets,
    from_fn,
    identity_buckets,
    radix_buckets,
    range_buckets,
)
from repro.core.pipeline import (
    MultisplitPlan,
    MultisplitResult,
    backend_for,
    make_batched_plan,
    make_plan,
    make_segmented_plan,
    set_autotune,
)
from repro.core.multisplit import _empty_segmented_result
from repro.core.sort import radix_sort, segmented_radix_sort
from repro.runtime import resilience as _rz
from repro.runtime import tracing
from repro.runtime.resilience import set_strict, set_verify

Array = jnp.ndarray

__all__ = [
    # bucket specs (hashable, pytree-static, kernel-fusable)
    "BucketSpec", "BitfieldSpec", "CallableSpec", "DeltaSpec", "EvenSpec",
    "IdentitySpec", "RangeSpec",
    "as_spec", "delta_buckets", "even_buckets", "from_fn",
    "identity_buckets", "radix_buckets", "range_buckets",
    # results
    "MultisplitResult",
    # operators
    "multisplit", "multisplit_key_value", "segmented_multisplit",
    "histogram", "radix_sort", "segmented_radix_sort",
    # tuning
    "set_autotune",
    # resilience (DESIGN.md §17)
    "set_strict", "set_verify",
]


def _out_batched(res: MultisplitResult) -> MultisplitResult:
    """out_batched pytree for a custom_vmap rule: True per present field."""
    return MultisplitResult(
        None if res.keys is None else True,
        None if res.values is None else True,
        True, True,
        None if res.permutation is None else True,
    )


def _broadcast_unbatched(x: Array, batched: bool, axis_size: int) -> Array:
    if batched:
        return x
    return jnp.broadcast_to(x[None], (axis_size,) + x.shape)


def _traced(*arrays) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in arrays if a is not None)


class _PlanOp(NamedTuple):
    """A resolved plan and the transform-aware op over it."""

    plan: MultisplitPlan
    op: Callable

    def __call__(self, *arrays):
        """Under a transformation, the op with its vmap and vjp rules.
        Eagerly, the plan traced and then evaluated, as the op runs it, with
        each stage's span open while its equations run."""
        if _traced(*arrays):
            return self.op(*arrays)
        return tracing.run_staged(self.plan, *arrays)


def _build_flat_op(spec: BucketSpec, n: int, method: str, backend: str,
                   tile: Optional[int], mode: str, family: Optional[str]):
    """The key-only op for one (spec, n, config): a custom_vmap-wrapped flat
    plan whose vmap rule IS the batched plan (one launch, DESIGN.md §9)."""
    plan = make_plan(
        n, spec.num_buckets, method=method, backend=backend, tile=tile,
        bucket_fn=spec, mode=mode, family=family,
    )

    @custom_batching.custom_vmap
    def op(keys):
        return plan(keys)

    @op.def_vmap
    def _rule(axis_size, in_batched, keys):  # noqa: ANN001 - jax rule signature
        keys = _broadcast_unbatched(keys, in_batched[0], axis_size)
        bplan = make_batched_plan(
            axis_size, n, spec.num_buckets, method=method, backend=backend,
            tile=tile, bucket_fn=spec, mode=mode, family=family,
        )
        res = bplan(keys)
        return res, _out_batched(res)

    return _PlanOp(plan, op)


# Declarative specs hash by VALUE, so the cache is exact and bounded by the
# distinct (spec, shape, config) set.  CallableSpec hashes by function
# identity — caching it would both miss for per-call closures and pin the
# closure (and anything it captures) for the module lifetime — so callables
# take the uncached builder.
_flat_op_cached = functools.lru_cache(maxsize=512)(_build_flat_op)


def _lookup(cached, build, spec, *config):
    """The op for ``(spec, *config)`` from its cache (built afresh for a
    CallableSpec), inside a ``repro.plan`` span whose ``hit`` says whether
    the cache held it."""
    with tracing.span("repro.plan") as sp:
        if isinstance(spec, CallableSpec):
            sp.set(hit=False)
            return build(spec, *config)
        hits = cached.cache_info().hits if sp else 0
        op = cached(spec, *config)
        if sp:
            sp.set(hit=cached.cache_info().hits > hits)
        return op


def _flat_op(spec, n, method, backend, tile, mode, family):
    return _lookup(_flat_op_cached, _build_flat_op, spec, n, method, backend,
                   tile, mode, family)


def _ct_gather(ct_leaf, perm):
    """One cotangent leaf of the kv backward pass: the inverse gather of the
    forward permutation (``d_in[i] = ct_out[perm[i]]``); integer primals get
    their mandated float0 zero."""
    if ct_leaf.dtype == jax.dtypes.float0:
        return np.zeros(np.shape(ct_leaf), jax.dtypes.float0)
    return jnp.take_along_axis(ct_leaf, perm, axis=-1)


def _build_kv_op(spec: BucketSpec, n: int, method: str, backend: str,
                 tile: Optional[int], family: Optional[str]):
    """The key-value op: custom_vjp (backward = inverse gather of the
    forward permutation) over a custom_vmap inner (batched-plan vmap rule),
    so grad, vmap, and vmap-of-grad all hit the intended paths."""
    plan = make_plan(
        n, spec.num_buckets, method=method, key_value=True, backend=backend,
        tile=tile, bucket_fn=spec, family=family,
    )

    @custom_batching.custom_vmap
    def inner(keys, values):
        return plan(keys, values)

    @inner.def_vmap
    def _rule(axis_size, in_batched, keys, values):  # noqa: ANN001
        keys = _broadcast_unbatched(keys, in_batched[0], axis_size)
        values = _broadcast_unbatched(values, in_batched[1], axis_size)
        bplan = make_batched_plan(
            axis_size, n, spec.num_buckets, method=method, key_value=True,
            backend=backend, tile=tile, bucket_fn=spec, family=family,
        )
        res = bplan(keys, values)
        return res, _out_batched(res)

    @jax.custom_vjp
    def op(keys, values):
        return inner(keys, values)

    def fwd(keys, values):
        res = inner(keys, values)
        return res, (res.permutation,)

    def bwd(residuals, ct):
        (perm,) = residuals
        # out[perm[i]] = in[i]  =>  d_in[i] = ct_out[perm[i]]: ONE gather.
        # Cotangents of the integer outputs (counts/starts/perm) are float0
        # and contribute nothing by construction.
        return _ct_gather(ct.keys, perm), _ct_gather(ct.values, perm)

    op.defvjp(fwd, bwd)
    return _PlanOp(plan, op)


_kv_op_cached = functools.lru_cache(maxsize=512)(_build_kv_op)


def _kv_op(spec, n, method, backend, tile, family):
    return _lookup(_kv_op_cached, _build_kv_op, spec, n, method, backend,
                   tile, family)


def _check_flat(keys: Array, what: str) -> None:
    if keys.ndim != 1:
        raise ValueError(
            f"{what} takes rank-1 keys (got shape {keys.shape}); batch with "
            f"jax.vmap({what}) — it dispatches to ONE batched-plan launch"
        )


def _resilient(
    run, keys: Array, values: Optional[Array], spec: BucketSpec, *,
    op: str, n: int, method: str, backend: Optional[str], tile: Optional[int],
    key_value: bool, mode: str, segments: Optional[int] = None,
    segment_starts=None,
):
    """Route one eager facade call through the degradation ladder + runtime
    verification (DESIGN.md §17): ``run(backend, tile)`` re-executes the op
    on any rung.  Under a jax trace the ladder is bypassed — exceptions
    cannot cross a trace, and the transform rules (vmap/jit/grad) must see
    the plain op.  Eagerly the call is the ``repro.op`` span ``op``, with
    the backend it starts on and whether the default chose it (``auto``).
    ``backend=None`` takes :func:`~repro.core.pipeline.default_backend`."""
    auto = backend is None
    backend = backend_for(backend, n, keys.dtype)
    if _traced(keys, values, segment_starts):
        return run(backend, tile)
    m_eff = spec.num_buckets * (segments or 1)
    ctx = _rz.DispatchContext(
        spec_name=getattr(spec, "name", type(spec).__name__),
        shape=tuple(keys.shape), num_buckets=spec.num_buckets,
        method=method, key_value=key_value, mode=mode,
        layout="segmented" if segments is not None else "flat",
    )

    def resolved_tile(be: str) -> int:
        from repro.core.pipeline.tiles import resolve_tile

        return resolve_tile(n, m_eff, method, key_value, be)

    def pin_tile(be: str, t: int) -> None:
        from repro.core.pipeline.tiles import pin_tile as _pin

        _pin(n, m_eff, method, key_value, be, t)

    def verifier(res, be: str) -> None:
        _rz.verify_result(
            res, keys=keys, spec=spec, n=n, values=values,
            segment_starts=segment_starts, mode=mode, backend=be, ctx=ctx,
        )

    with tracing.span("repro.op", op=op, n=n, m=spec.num_buckets,
                      key_value=key_value, backend=backend, auto=auto):
        return _rz.dispatch(
            run, ctx, backend=backend, tile=tile, resolved_tile=resolved_tile,
            pin_tile=pin_tile, verifier=verifier,
        )




def multisplit(
    keys: Array,
    spec: BucketSpec,
    values: Optional[Array] = None,
    *,
    method: str = "bms",
    backend: Optional[str] = None,
    tile: Optional[int] = None,
    mode: str = "reorder",
    family: Optional[str] = None,
) -> MultisplitResult:
    """Stable multisplit of ``keys`` (and optional ``values``) into the
    buckets of a declarative ``spec`` (paper §3.1).

    Transform-native: ``jax.vmap(ops.multisplit)`` runs the whole batch as
    ONE batched-plan launch (bitwise equal to the per-row loop); with
    ``values`` the op is differentiable (see :func:`multisplit_key_value`);
    equal specs share one trace under ``jit``.  ``mode`` selects a partial
    pipeline (``counts_only`` / ``positions_only``, key-only — DESIGN.md
    §10); ``family`` pins the kernel family (``"onehot"``/``"packed"``,
    DESIGN.md §12 — bitwise identical, cost only; ``None`` auto-resolves).
    """
    spec = as_spec(spec)
    _check_flat(keys, "ops.multisplit")
    if values is not None:
        if mode != "reorder":
            raise ValueError(f"mode={mode!r} never touches values")
        return multisplit_key_value(
            keys, values, spec, method=method, backend=backend, tile=tile,
            family=family,
        )
    n = keys.shape[0]
    return _resilient(
        lambda be, tl: _flat_op(spec, n, method, be, tl, mode, family)(keys),
        keys, None, spec, op="multisplit", n=n, method=method,
        backend=backend, tile=tile, key_value=False, mode=mode,
    )


def multisplit_key_value(
    keys: Array,
    values: Array,
    spec: BucketSpec,
    *,
    method: str = "bms",
    backend: Optional[str] = None,
    tile: Optional[int] = None,
    family: Optional[str] = None,
) -> MultisplitResult:
    """Key-value multisplit, differentiable in ``values`` (and in ``keys``
    when they are inexact): the backward pass is the INVERSE GATHER of the
    forward permutation — ``d_in[i] = ct_out[perm[i]]``, one ``take`` per
    operand, no dense one-hot and no scatter transpose.

    ``jax.vmap`` of this op (with or without ``jax.grad``) also dispatches
    to ONE batched-plan launch via the inner custom-vmap rule.
    """
    spec = as_spec(spec)
    _check_flat(keys, "ops.multisplit_key_value")
    n = keys.shape[0]
    return _resilient(
        lambda be, tl: _kv_op(spec, n, method, be, tl, family)(keys, values),
        keys, values, spec, op="multisplit_key_value", n=n, method=method,
        backend=backend, tile=tile, key_value=True, mode="reorder",
    )


def segmented_multisplit(
    keys: Array,
    spec: BucketSpec,
    segment_starts,
    values: Optional[Array] = None,
    *,
    method: str = "bms",
    backend: Optional[str] = None,
    tile: Optional[int] = None,
    mode: str = "reorder",
    family: Optional[str] = None,
) -> MultisplitResult:
    """Multisplit every ragged segment of flat ``keys`` independently in ONE
    plan launch (DESIGN.md §9): ``segment_starts`` is the (s,) ascending
    start-offset vector (``segment_starts[0] == 0``; empty segments
    allowed, and ``s == 0`` with empty keys — a zero-request serving step —
    returns (0, m) counts).  Bitwise identical to per-segment
    :func:`multisplit` calls; counts/starts come back (s, m)
    segment-local."""
    spec = as_spec(spec)
    _check_flat(keys, "ops.segmented_multisplit")
    if values is not None and mode != "reorder":
        raise ValueError(f"mode={mode!r} never touches values")
    seg = jnp.asarray(segment_starts, jnp.int32)
    if seg.shape[0] == 0:        # zero-request step (ISSUE 9 S1)
        return _empty_segmented_result(keys, values, spec.num_buckets, mode)
    n, s = keys.shape[0], int(seg.shape[0])

    def run(be, tl):
        with tracing.span("repro.plan", hit=False):       # not cached
            plan = make_segmented_plan(
                n, s, spec.num_buckets, method=method,
                key_value=values is not None, backend=be, tile=tl,
                bucket_fn=spec, mode=mode, family=family,
            )
        return plan(keys, values, segment_starts=seg)

    return _resilient(
        run, keys, values, spec, op="segmented_multisplit", n=n,
        method=method, backend=backend, tile=tile,
        key_value=values is not None, mode=mode, segments=s,
        segment_starts=seg,
    )


def histogram(
    keys: Array,
    spec: BucketSpec,
    *,
    backend: Optional[str] = None,
    tile: Optional[int] = None,
    family: Optional[str] = None,
) -> Array:
    """Device-wide bucket counts (paper §7.3): the ``counts_only`` partial
    pipeline — {prescan, tree-reduce}, no scan, no scatter."""
    spec = as_spec(spec)
    _check_flat(keys, "ops.histogram")
    return multisplit(
        keys, spec, backend=backend, tile=tile, mode="counts_only",
        family=family,
    ).bucket_counts
