"""Bucket specs (paper §3.1, §6 "Bucket identification") — declarative,
hashable, transform-native.

The paper's defining feature is that *the function that categorizes an
element into a bucket is provided by the programmer*.  PR-1..3 carried that
function as an opaque closure, which every backend
had to evaluate into a full n-sized label array before the pipeline started
— the exact "more expensive data movements" overhead the paper charges the
sort-based baselines with (§3.4) — and which defeated jit caching (closures
hash by identity, so every identifier instance retraced).

This module replaces the closure-first identifier with a hierarchy of
declarative :class:`BucketSpec` dataclasses:

* **hashable / comparable by value** — two ``delta_buckets(32)`` calls
  produce EQUAL specs, so jit caches, the kernel-wrapper jit cache and the
  ``repro.ops`` op cache all hit instead of retracing;
* **pytree-registered as static leaves** — a spec passed through ``jit`` /
  ``vmap`` / ``grad`` rides in the treedef (no tracer, no retrace, no
  batching axis), which is what makes the ``repro.ops`` transform rules
  possible;
* **fusable** — every non-callable spec exposes :meth:`BucketSpec.emit`
  written in plain vectorized jnp, which the tile kernels evaluate
  *in-register inside the kernel* (``kernels/multisplit_tile.py``); the
  n-sized label array never exists for these specs.  The paper's radix digit
  is just :class:`BitfieldSpec`, its splitter buckets :class:`RangeSpec`
  (cf. GPU sample sort, arXiv:0909.5649).

:class:`CallableSpec` remains the escape hatch for arbitrary user functions
(the paper's "prime vs composite"); it is the only spec backends must
materialize labels for.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jnp.ndarray


def _register_static(cls):
    """Register a frozen spec dataclass as a LEAFLESS pytree: the whole spec
    rides in the treedef (hashed/compared by value), so jit keys on it like a
    static argument and vmap/grad pass it through untouched."""
    jax.tree_util.register_pytree_node(cls, lambda s: ((), s), lambda s, _: s)
    return cls


class BucketSpec:
    """Base class: a declarative bucket identifier ``emit(keys) -> ids``.

    Concrete specs are frozen dataclasses (value-hashable).  ``fusable``
    marks specs whose :meth:`emit` is plain vectorized jnp safe to trace
    inside a tile kernel; :meth:`pad_key` returns a key value whose bucket is
    ``num_buckets - 1`` (layout pads ride in the last bucket and are sliced
    off the output tail).
    """

    fusable: bool = True
    # concrete specs provide ``num_buckets`` (field or property) and ``name``
    # (field or property); the base deliberately declares neither so frozen
    # dataclass subclasses can use plain fields.

    def emit(self, keys: Array) -> Array:
        """int32 bucket ids in ``[0, num_buckets)``; shape-preserving."""
        raise NotImplementedError

    def emit_in_kernel(self, keys: Array) -> Array:
        """:meth:`emit` as traced INSIDE a tile kernel.  Defaults to
        ``emit``; specs whose host-side form uses ops a pallas kernel cannot
        lower (or captured constant arrays) override this with an
        equivalent vector-op form."""
        return self.emit(keys)

    def pad_key(self, dtype):
        """A key value that lands in bucket ``num_buckets - 1``: the dtype
        maximum (every spec here is monotone and clamps its top bucket)."""
        dtype = jnp.dtype(dtype)
        if jnp.issubdtype(dtype, jnp.unsignedinteger):
            return (1 << (8 * dtype.itemsize)) - 1
        if jnp.issubdtype(dtype, jnp.floating):
            return float(jnp.finfo(dtype).max)
        return int(jnp.iinfo(dtype).max)

    # identifiers have always been callable (``bf(keys)``); keep it.
    def __call__(self, keys: Array) -> Array:
        return self.emit(keys)


@_register_static
@dataclasses.dataclass(frozen=True)
class DeltaSpec(BucketSpec):
    """Equal-width buckets over the key domain: ``f(u) = u // delta``
    (paper §6), clamped into range so any key ≥ key_max lands in the last
    bucket (this also makes the all-ones pad key safe)."""

    num_buckets: int
    key_max: int = 2**30

    @property
    def delta(self) -> int:
        return max(1, self.key_max // self.num_buckets)

    def emit(self, keys: Array) -> Array:
        ids = keys.astype(jnp.uint32) // jnp.uint32(self.delta)
        last = jnp.uint32(self.num_buckets - 1)
        # a compare-select, not jnp.minimum: Mosaic lowers no unsigned min
        return jnp.where(ids < last, ids, last).astype(jnp.int32)

    @property
    def name(self) -> str:
        return f"delta{self.num_buckets}"


@_register_static
@dataclasses.dataclass(frozen=True)
class IdentitySpec(BucketSpec):
    """Keys are already bucket ids: ``f(u) = u`` (paper §7.1)."""

    num_buckets: int

    def emit(self, keys: Array) -> Array:
        return keys.astype(jnp.int32)

    def pad_key(self, dtype):
        return self.num_buckets - 1                # all-ones would leave range

    @property
    def name(self) -> str:
        return f"identity{self.num_buckets}"


@_register_static
@dataclasses.dataclass(frozen=True)
class BitfieldSpec(BucketSpec):
    """``f(u) = (u >> shift) & (2^bits - 1)`` — one LSD radix-sort digit
    (paper §7.1).  The chained :class:`~repro.core.pipeline.radix.
    RadixPipeline` is one BitfieldSpec plan per pass; the all-ones pad key
    has digit ``m - 1`` in EVERY pass, which is what lets the chained sort
    pad once."""

    shift: int
    bits: int

    @property
    def num_buckets(self) -> int:
        return 1 << self.bits

    def emit(self, keys: Array) -> Array:
        self._check_integer(keys.dtype)
        u = keys.astype(jnp.uint32)
        mask = jnp.uint32((1 << self.bits) - 1)
        return ((u >> jnp.uint32(self.shift)) & mask).astype(jnp.int32)

    @staticmethod
    def _check_integer(dtype) -> None:
        """Digits are BIT FIELDS of the key word; ``astype`` on a float key
        is a VALUE conversion, and the float pad lane has no all-ones digit
        pattern (``pad_key`` used to return ``-1``, i.e. ``-1.0``, which is
        NOT digit m-1 — it silently corrupted the pad lane)."""
        if not jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
            raise TypeError(
                f"radix digit buckets (BitfieldSpec) require integer keys, got "
                f"{jnp.dtype(dtype)}; bitfield digits of float keys are value "
                f"conversions, not bit patterns — reinterpret the buffer "
                f"(e.g. jax.lax.bitcast_convert_type) to uint32 first"
            )

    def pad_key(self, dtype):
        """The ALL-ONES bit pattern (not the signed max): its digit is m-1
        in every pass, the chained-radix pad invariant.  Raises
        :class:`TypeError` for float dtypes (see :meth:`_check_integer`)."""
        dtype = jnp.dtype(dtype)
        self._check_integer(dtype)
        if jnp.issubdtype(dtype, jnp.unsignedinteger):
            return (1 << (8 * dtype.itemsize)) - 1
        return -1

    @property
    def name(self) -> str:
        return f"radix[{self.shift}:{self.shift + self.bits}]"


@_register_static
@dataclasses.dataclass(frozen=True)
class RangeSpec(BucketSpec):
    """Splitter buckets (paper §7.3 "Range Histogram"; the sample-sort
    bucket function of arXiv:0909.5649): key u lands in bucket j s.t.
    ``splitters[j-1] <= u < splitters[j]``, ``m = len(splitters) + 1``.

    Splitters are canonicalized to a SORTED tuple at construction (unsorted
    splitters silently produced wrong buckets pre-PR-4) and compared in the
    KEY dtype at emit time, so uint32 keys above the last splitter — up to
    the dtype max — never wrap through a signed promotion.
    """

    splitters: Tuple

    def __post_init__(self):
        sp = np.asarray(self.splitters)
        if sp.ndim != 1:
            raise ValueError(f"splitters must be 1-D, got shape {sp.shape}")
        if np.isnan(sp.astype(np.float64)).any():
            raise ValueError("splitters must not contain NaN")
        object.__setattr__(self, "splitters", tuple(np.sort(sp).tolist()))

    @property
    def num_buckets(self) -> int:
        return len(self.splitters) + 1

    def _compare_plane(self, key_dtype):
        """(compare_dtype, splitter_values): integer keys with integral
        splitters compare in the KEY dtype (no promotion, so uint32 keys up
        to the dtype max never wrap through a signed intermediate; splitters
        outside the key dtype's range are REJECTED — they would make the
        last bucket unreachable, breaking the pad-in-bucket-m-1 layout
        invariant); anything involving fractional splitters or float keys
        compares in float."""
        integral = all(float(s) == int(s) for s in self.splitters)
        if jnp.issubdtype(key_dtype, jnp.integer) and integral:
            info = jnp.iinfo(key_dtype)
            for s in self.splitters:
                if not info.min <= int(s) <= info.max:
                    raise ValueError(
                        f"splitter {s} is out of range for {np.dtype(key_dtype)} "
                        f"keys [{info.min}, {info.max}]"
                    )
            return np.dtype(key_dtype), [int(s) for s in self.splitters]
        plane = key_dtype if jnp.issubdtype(key_dtype, jnp.floating) else jnp.float32
        return np.dtype(plane), [float(s) for s in self.splitters]

    def emit(self, keys: Array) -> Array:
        if not self.splitters:
            return jnp.zeros(keys.shape, jnp.int32)
        # O(n log s) binary search in the compare plane (splitters are
        # canonically sorted); side="right" = count of splitters <= u.
        plane, vals = self._compare_plane(keys.dtype)
        return jnp.searchsorted(
            jnp.asarray(vals, plane), keys.astype(plane), side="right"
        ).astype(jnp.int32)

    def emit_in_kernel(self, keys: Array) -> Array:
        if not self.splitters:
            return jnp.zeros(keys.shape, jnp.int32)
        # In-kernel form of emit.  A pallas kernel can neither lower
        # searchsorted nor capture a constant splitter ARRAY — only scalars
        # fold — so each splitter enters as one PLANE-dtype scalar compare
        # (a raw Python int would weak-type to int32 and overflow for
        # splitters above 2^31).  The bucket id is the POPCOUNT of those
        # compares; summing them pairwise as a balanced binary tree keeps
        # the dependency depth at O(log s) vector adds (vs O(s) for a serial
        # chain of adds), which is what unblocks large splitter
        # counts (s = 255+, the sample-sort regime) — a per-element binary
        # SEARCH over the splitter domain is impossible without a gather or
        # a captured array, and would cost O(s) selects per probe anyway.
        plane, vals = self._compare_plane(keys.dtype)
        kc = keys.astype(plane)
        parts = [
            (kc >= np.asarray(s, plane)[()]).astype(jnp.int32) for s in vals
        ]
        while len(parts) > 1:
            nxt = [a + b for a, b in zip(parts[0::2], parts[1::2])]
            if len(parts) % 2:
                nxt.append(parts[-1])
            parts = nxt
        return parts[0]

    @property
    def name(self) -> str:
        return f"range{self.num_buckets}"


@_register_static
@dataclasses.dataclass(frozen=True)
class EvenSpec(BucketSpec):
    """Evenly spaced float buckets (paper §7.3 "Even Histogram")."""

    lo: float
    hi: float
    num_buckets: int

    def emit(self, keys: Array) -> Array:
        width = (self.hi - self.lo) / self.num_buckets
        ids = jnp.floor((keys - self.lo) / width)
        # clip in FLOAT domain: the +inf/fmax pad key must land in the last
        # bucket, and float->int conversion of out-of-range values is
        # platform-defined.
        ids = jnp.clip(ids, 0, self.num_buckets - 1)
        # NaN keys survive both floor and clip (clip(NaN) is NaN), and
        # NaN->int conversion is platform-defined (observed: bucket 0).
        # Route them deterministically into the LAST bucket, matching the
        # +inf pad sentinel.  ``ids != ids`` is the NaN test that stays a
        # plain vector compare in-kernel and is False on integer keys.
        ids = jnp.where(ids != ids, self.num_buckets - 1, ids)
        return ids.astype(jnp.int32)

    @property
    def name(self) -> str:
        return f"even{self.num_buckets}"


@_register_static
@dataclasses.dataclass(frozen=True)
class CallableSpec(BucketSpec):
    """Escape hatch: an arbitrary user function (the paper's "prime vs
    composite" etc.).  Not fusable — backends materialize its labels
    host-side — and hashed by function identity, so distinct closures
    retrace (use a declarative spec to share traces)."""

    fn: Callable[[Array], Array]
    num_buckets: int
    name: str = "custom"

    fusable = False

    def emit(self, keys: Array) -> Array:
        return self.fn(keys).astype(jnp.int32)

    def pad_key(self, dtype):
        # the base-class contract (pad lands in bucket m-1) cannot be
        # guaranteed for an arbitrary fn; the layout pads CallableSpec plans
        # on the LABEL side (ids padded with m-1), never on the key side.
        raise NotImplementedError(
            f"no pad key exists for the arbitrary bucket function {self.name!r}; "
            "callable specs pad labels (not keys)"
        )


def delta_buckets(num_buckets: int, key_max: int = 2**30) -> DeltaSpec:
    """Equal-width buckets over the key domain: ``f(u) = u // delta`` (§6)."""
    return DeltaSpec(num_buckets, key_max)


def identity_buckets(num_buckets: int) -> IdentitySpec:
    """Keys are already bucket ids: ``f(u) = u`` (paper §7.1)."""
    return IdentitySpec(num_buckets)


def radix_buckets(pass_idx: int, radix_bits: int) -> BitfieldSpec:
    """``f_k(u) = (u >> k*r) & (2^r - 1)`` — one LSD radix digit (§7.1)."""
    return BitfieldSpec(pass_idx * radix_bits, radix_bits)


def range_buckets(splitters) -> RangeSpec:
    """Arbitrary splitter buckets (paper §7.3 "Range Histogram").

    ``splitters`` may be a sequence or array; it is validated and SORTED
    into the spec (``m = len(splitters) + 1``)."""
    sp = np.asarray(splitters)
    return RangeSpec(tuple(sp.tolist()))


def even_buckets(lo: float, hi: float, num_buckets: int) -> EvenSpec:
    """Evenly spaced float buckets (paper §7.3 "Even Histogram")."""
    return EvenSpec(float(lo), float(hi), num_buckets)


def from_fn(fn: Callable[[Array], Array], num_buckets: int, name: str = "user") -> CallableSpec:
    """Wrap an arbitrary user function (the paper's "prime vs composite")."""
    return CallableSpec(fn, num_buckets, name=name)


def as_spec(spec) -> BucketSpec:
    """Coerce a user-supplied identifier into a :class:`BucketSpec`.

    Accepts any spec as-is;
    a bare callable is wrapped iff it carries a ``num_buckets`` attribute.
    """
    if isinstance(spec, BucketSpec):
        return spec
    if callable(spec) and hasattr(spec, "num_buckets"):
        return CallableSpec(spec, int(spec.num_buckets))
    raise TypeError(
        f"expected a BucketSpec (see repro.core.identifiers), got {spec!r}"
    )
