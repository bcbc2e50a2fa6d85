"""Multisplit-based radix sort (paper §7.1) and the sort-based baselines (§3).

* ``radix_sort``           — LSD radix sort built from iterated multisplit
                             with identity-bit buckets ``f_k``; the paper's
                             "multisplit-sort". Executes as a CHAINED
                             :class:`~repro.core.pipeline.radix.RadixPipeline`
                             (DESIGN.md §10): tiles resolved once, buffers
                             padded once, ping-pong across digit passes.
* ``rb_sort_multisplit``   — the paper's *reduced-bit sort* baseline (§3.4):
                             multisplit implemented by sorting ⌈log m⌉-bit
                             labels with the platform sort primitive
                             (``jax.lax.sort`` standing in for CUB).
* ``direct_sort_multisplit`` — the §3.3 baseline: a full key sort, valid
                             only for monotone bucket identifiers, and
                             non-stable as a multisplit.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import multisplit as ms
from repro.core.identifiers import BucketSpec
from repro.core.pipeline import RadixPipeline, backend_for
from repro.runtime import tracing

Array = jnp.ndarray


def _op_span(op: str, n: int, radix_bits: int, keys, values, backend: str,
             auto: bool):
    """The ``repro.op`` span of an eager sort; none under a transformation."""
    if any(isinstance(a, jax.core.Tracer) for a in (keys, values)):
        return tracing.OFF
    return tracing.span("repro.op", op=op, n=n, m=1 << radix_bits,
                        key_value=values is not None, backend=backend,
                        auto=auto)


def radix_sort(
    keys: Array,
    values: Optional[Array] = None,
    *,
    radix_bits: int = 8,
    key_bits: int = 32,
    method: str = "bms",
    backend: Optional[str] = None,
    tile: Optional[int] = None,
    family: Optional[str] = None,
    fuse_digits: bool = False,
) -> Tuple[Array, Optional[Array]]:
    """Sort uint32 keys with ⌈key_bits/radix_bits⌉ multisplit passes (§7.1).

    Stable. ``radix_bits=8`` means each pass is a 256-bucket multisplit —
    the paper's large-m regime; Table 8 sweeps r in [4, 8].

    Executes as ONE chained :class:`~repro.core.pipeline.radix.RadixPipeline`
    (DESIGN.md §10): tiles are resolved once, the keys/values buffers are
    padded once with the all-ones sentinel (digit m−1 in every pass) and
    stay resident across all digit passes — no per-pass re-pad/re-tile/slice.
    On kernel backends the digit ``f_k(u) = (u >> k·r) & (2^r − 1)`` is
    extracted INSIDE the fused kernels, so no label array is ever
    materialized host-side — the §3.4 RB-sort overhead the paper's
    multisplit-sort avoids (DESIGN.md §5).

    2-D ``(b, n)`` keys sort every row independently through BATCHED radix
    plans (DESIGN.md §9): still one kernel launch per pass, covering all
    rows.

    ``fuse_digits=True`` (DESIGN.md §13) runs adjacent digit passes as FUSED
    PAIRS: one sweep per pair — two digit solves around an in-VMEM reorder
    per tile residency, one HBM scatter per pair instead of per digit
    (r=8 → 2 sweeps instead of 4, plus a trailing single pass for odd
    schedules). Bitwise identical to the unfused sort on every backend.

    With no ``backend`` named the sort runs where
    :func:`~repro.core.pipeline.default_backend` sends it: the compiled
    ``pallas`` kernels on a TPU for 32-bit keys, ``vmap`` otherwise.
    """
    if keys.ndim == 2:
        batch, n = keys.shape
    else:
        batch, n = None, keys.shape[0]
    resolved = backend_for(backend, n, keys.dtype)
    pipe = RadixPipeline(
        n,
        radix_bits=radix_bits,
        key_bits=key_bits,
        method=method,
        key_value=values is not None,
        backend=resolved,
        tile=tile,
        batch=batch,
        family=family,
        fuse_digits=fuse_digits,
    )
    with _op_span("radix_sort", n, radix_bits, keys, values, resolved,
                  backend is None):
        return pipe(keys, values)


def segmented_radix_sort(
    keys: Array,
    segment_starts,
    values: Optional[Array] = None,
    *,
    radix_bits: int = 8,
    key_bits: int = 32,
    method: str = "bms",
    backend: Optional[str] = None,
    tile: Optional[int] = None,
    family: Optional[str] = None,
    fuse_digits: bool = False,
) -> Tuple[Array, Optional[Array]]:
    """Sort every ragged segment of flat uint32 ``keys`` independently, in
    ONE chained sequence of ⌈key_bits/radix_bits⌉ segmented multisplit
    passes (DESIGN.md §9/§10) — not one pass sequence per segment.

    ``segment_starts`` is the (s,) ascending start-offset vector of
    :func:`repro.core.multisplit.segmented_multisplit`. Segment membership
    is invariant across passes (elements never cross segment boundaries), so
    the chained pipeline computes the position-keyed segment buffer once and
    keeps it — with the padded keys/values — resident for all passes.
    Stable; bitwise identical to slicing out each segment and running
    :func:`radix_sort` on it. The backend defaults as in :func:`radix_sort`.
    """
    resolved = backend_for(backend, keys.shape[0], keys.dtype)
    seg = jnp.asarray(segment_starts, jnp.int32)
    pipe = RadixPipeline(
        keys.shape[0],
        radix_bits=radix_bits,
        key_bits=key_bits,
        method=method,
        key_value=values is not None,
        backend=resolved,
        tile=tile,
        segments=int(seg.shape[0]),
        family=family,
        fuse_digits=fuse_digits,
    )
    with _op_span("segmented_radix_sort", keys.shape[0], radix_bits, keys, values,
                  resolved, backend is None):
        return pipe(keys, values, segment_starts=seg)


def rb_sort_multisplit(
    keys: Array,
    bucket_fn: BucketSpec,
    values: Optional[Array] = None,
) -> ms.MultisplitResult:
    """Reduced-bit-sort baseline (§3.4): sort (label, payload) by label.

    Key-only: sort (label, key) pairs. Key-value: pack key+value into the
    payload (the paper packs into a 64-bit word; ``jax.lax.sort`` natively
    sorts multiple operands, which is the same trick without the pack).
    """
    m = bucket_fn.num_buckets
    labels = bucket_fn(keys)
    if values is None:
        labels_s, keys_s = jax.lax.sort((labels, keys), num_keys=1, is_stable=True)
        values_s = None
    else:
        labels_s, keys_s, values_s = jax.lax.sort(
            (labels, keys, values), num_keys=1, is_stable=True
        )
    one_hot = (labels_s[:, None] == jnp.arange(m)[None, :]).astype(jnp.int32)
    counts = one_hot.sum(axis=0)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)]
    )
    perm = jnp.zeros_like(labels).at[jnp.argsort(labels, stable=True)].set(
        jnp.arange(labels.shape[0], dtype=jnp.int32)
    )
    return ms.MultisplitResult(keys_s, values_s, starts, counts.astype(jnp.int32), perm)


def direct_sort_multisplit(
    keys: Array, values: Optional[Array] = None
) -> Tuple[Array, Optional[Array]]:
    """§3.3 baseline: full sort of the keys themselves (monotone buckets only)."""
    if values is None:
        return jax.lax.sort(keys), None
    keys_s, values_s = jax.lax.sort((keys, values), num_keys=1)
    return keys_s, values_s
