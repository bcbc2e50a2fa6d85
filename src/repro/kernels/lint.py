"""Jaxpr lint: prove the compiled kernel path is gather/scatter-free.

Mosaic (the TPU Pallas compiler, ``interpret=False``) does not lower
in-kernel gathers (``x[ids]``, ``take_along_axis``), scatters
(``.at[ids].set/add``) or tensor-indexed dynamic slices.  DESIGN.md §15
replaces every such access in the tile stage bodies with oblivious,
lane-parallel forms (masked one-hot selects, 16-bit rank planes,
permutation matmuls).  This module is the *proof obligation*: it traces
every Pallas kernel entry point exactly as the pipeline invokes it
(oblivious defaults), walks the jaxpr recursively, and asserts that no
forbidden primitive appears INSIDE any ``pallas_call`` body.

Tracing is execution-free and identical for ``interpret=True`` and the
compiled path — the jaxpr is the same program Mosaic would receive — so
the lint runs on any host, no TPU required.  Gathers OUTSIDE kernels
(host-side padding, the vmap oracle stages) are deliberately not flagged:
XLA lowers them fine and they are the fast host path.

Run as a module for the CI step::

    python -m repro.kernels.lint          # report + exit 1 on violation
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.extend import core as _core

from repro.core.identifiers import BitfieldSpec, EvenSpec, RangeSpec
from repro.kernels import multisplit_tile as _mst
from repro.kernels import radix_pass as _rp

# Primitives Mosaic cannot lower inside a TPU kernel body. ``cumsum`` and
# iota/broadcast compares are NOT here — they are the allowed oblivious
# vocabulary (DESIGN.md §15).
FORBIDDEN_PRIMITIVES = frozenset(
    {"gather", "scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max"}
)
# dynamic_slice / dynamic_update_slice are forbidden only when a start
# operand is a tensor (rank > 0): scalar-start slices are static layout
# arithmetic, tensor starts are a gather in disguise.
_DYNAMIC_SLICE = {"dynamic_slice": 1, "dynamic_update_slice": 2}


@dataclasses.dataclass(frozen=True)
class LintResult:
    """Lint verdict for one kernel entry point."""

    name: str
    pallas_calls: int                 # pallas_call eqns seen in the trace
    kernel_primitives: Tuple[str, ...]  # sorted primitive names inside kernels
    violations: Tuple[str, ...]       # forbidden primitives found inside

    @property
    def ok(self) -> bool:
        return not self.violations and self.pallas_calls > 0


def _sub_jaxprs(params) -> List:
    """Every Jaxpr/ClosedJaxpr nested in an eqn's params (any structure)."""
    found = []

    def visit(v):
        if isinstance(v, _core.ClosedJaxpr):
            found.append(v.jaxpr)
        elif isinstance(v, _core.Jaxpr):
            found.append(v)
        elif isinstance(v, (list, tuple)):
            for x in v:
                visit(x)
        elif isinstance(v, dict):
            for x in v.values():
                visit(x)

    for v in params.values():
        visit(v)
    return found


def _walk(jaxpr, inside: bool, prims: set, violations: list, counter: list) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        is_pallas = name == "pallas_call"
        if is_pallas:
            counter[0] += 1
        if inside:
            prims.add(name)
            if name in FORBIDDEN_PRIMITIVES:
                violations.append(name)
            elif name in _DYNAMIC_SLICE:
                starts = eqn.invars[_DYNAMIC_SLICE[name]:]
                if any(getattr(v, "aval", None) is not None and v.aval.ndim > 0
                       for v in starts):
                    violations.append(f"{name}[tensor-start]")
        for sub in _sub_jaxprs(eqn.params):
            _walk(sub, inside or is_pallas, prims, violations, counter)


def lint_fn(fn: Callable, *args, name: str = "<fn>") -> LintResult:
    """Trace ``fn(*args)`` and lint every pallas_call body in the jaxpr."""
    closed = jax.make_jaxpr(fn)(*args)
    prims: set = set()
    violations: list = []
    counter = [0]
    _walk(closed.jaxpr, False, prims, violations, counter)
    return LintResult(
        name=name,
        pallas_calls=counter[0],
        kernel_primitives=tuple(sorted(prims)),
        violations=tuple(sorted(set(violations))),
    )


# ---------------------------------------------------------------------------
# Canonical entry-point registry: every Pallas door the pipeline dispatches
# through, traced with the shapes/flags the plan layer actually uses and the
# oblivious (compiled-path) defaults. Each value is a zero-arg thunk
# returning a LintResult, so registry construction stays trace-free.
# ---------------------------------------------------------------------------

_L, _T, _M = 2, 256, 16


def _ids():
    return jnp.zeros((_L, _T), jnp.int32)


def _keys():
    return jnp.zeros((_L, _T), jnp.uint32)


def _seg():
    return jnp.zeros((_L, _T), jnp.int32)


def _g(m):
    return jnp.zeros((_L, m), jnp.int32)


def _range_spec(s: int) -> RangeSpec:
    return RangeSpec(tuple(np.arange(1, s + 1, dtype=np.uint32) * 7))


def kernel_entry_points() -> Dict[str, Callable[[], LintResult]]:
    spec4 = BitfieldSpec(0, 4)
    even = EvenSpec(0.0, 1024.0, _M)
    pair = BitfieldSpec(0, 8)          # fused2 combined pair digit, m = 256
    ep: Dict[str, Callable[[], LintResult]] = {}

    def add(name, fn, *args):
        ep[name] = lambda: lint_fn(fn, *args, name=name)

    # dense strip kernels
    add("dense/histograms", lambda i: _mst.tile_histograms_pallas(i, _M), _ids())
    add("dense/positions",
        lambda i, g: _mst.tile_positions_pallas(i, g, _M), _ids(), _g(_M))
    add("dense/fused_kv",
        lambda i, g, k, v: _mst.fused_postscan_reorder_pallas(i, g, k, v, _M),
        _ids(), _g(_M), _keys(), _keys())
    add("dense/reorder",
        lambda i, k, v: _mst.tile_reorder_pallas(i, k, v, _M),
        _ids(), _keys(), _keys())

    # segmented strip kernels (cid = seg*m + bucket in-register)
    add("seg/histograms",
        lambda i, s: _mst.seg_tile_histograms_pallas(i, s, _M, 2),
        _ids(), _seg())
    add("seg/positions",
        lambda i, s, g: _mst.seg_tile_positions_pallas(i, s, g, _M, 2),
        _ids(), _seg(), _g(2 * _M))
    add("seg/fused_kv",
        lambda i, s, g, k, v: _mst.seg_fused_postscan_reorder_pallas(
            i, s, g, k, v, _M, 2),
        _ids(), _seg(), _g(2 * _M), _keys(), _keys())

    # fused-label (spec) kernels — bitfield, even and range-tree labels
    add("spec/histograms",
        lambda k: _mst.spec_tile_histograms_pallas(k, spec4), _keys())
    add("spec/positions",
        lambda k, g: _mst.spec_tile_positions_pallas(k, g, spec4),
        _keys(), _g(_M))
    add("spec/fused_kv",
        lambda k, g, v: _mst.spec_fused_postscan_reorder_pallas(k, g, v, spec4),
        _keys(), _g(_M), _keys())
    add("spec/bucket_ids_even",
        lambda k: _mst.spec_bucket_ids_pallas(k.astype(jnp.float32), even),
        _keys())
    add("spec/positions_range31",
        lambda k, g: _mst.spec_tile_positions_pallas(k, g, _range_spec(31)),
        _keys(), _g(32))
    add("spec/bucket_ids_range255",
        lambda k: _mst.spec_bucket_ids_pallas(k, _range_spec(255)), _keys())

    # segmented fused-label kernels
    add("seg_spec/histograms",
        lambda k, s: _mst.seg_spec_tile_histograms_pallas(k, s, spec4, 2),
        _keys(), _seg())
    add("seg_spec/positions",
        lambda k, s, g: _mst.seg_spec_tile_positions_pallas(k, s, g, spec4, 2),
        _keys(), _seg(), _g(2 * _M))
    add("seg_spec/fused_kv",
        lambda k, s, g, v: _mst.seg_spec_fused_postscan_reorder_pallas(
            k, s, g, v, spec4, 2),
        _keys(), _seg(), _g(2 * _M), _keys())

    # packed family (rank planes; histograms kernel is family-shared)
    add("packed/histograms",
        lambda i: _mst.packed_tile_histograms_pallas(i, _M), _ids())
    add("packed/positions",
        lambda i, g: _mst.packed_tile_positions_pallas(i, g, _M),
        _ids(), _g(_M))
    add("packed/positions_seg_spec",
        lambda k, s, g: _mst.packed_tile_positions_pallas(
            k, g, 0, spec=spec4, seg_tiled=s, num_segments=2),
        _keys(), _seg(), _g(2 * _M))
    add("packed/fused_kv",
        lambda i, g, k, v: _mst.packed_fused_postscan_reorder_pallas(
            i, g, k, v, num_buckets=_M),
        _ids(), _g(_M), _keys(), _keys())
    add("packed/fused_kv_seg_spec",
        lambda k, s, g, v: _mst.packed_fused_postscan_reorder_pallas(
            k, g, values_tiled=v, spec=spec4, seg_tiled=s, num_segments=2),
        _keys(), _seg(), _g(2 * _M), _keys())

    # fused two-digit family (pair digit, both stage families)
    add("fused2/histograms",
        lambda k: _mst.fused2_tile_histograms_pallas(k, pair), _keys())
    add("fused2/histograms_seg",
        lambda k, s: _mst.fused2_tile_histograms_pallas(
            k, pair, seg_tiled=s, num_segments=2),
        _keys(), _seg())
    add("fused2/positions_onehot",
        lambda k, g: _mst.fused2_tile_positions_pallas(k, g, pair, 4),
        _keys(), _g(256))
    add("fused2/positions_packed",
        lambda k, g: _mst.fused2_tile_positions_pallas(
            k, g, pair, 4, family="packed"),
        _keys(), _g(256))
    add("fused2/fused_kv_onehot_seg",
        lambda k, s, g, v: _mst.fused2_fused_postscan_reorder_pallas(
            k, g, v, spec=pair, split=4, seg_tiled=s, num_segments=2),
        _keys(), _seg(), _g(512), _keys())
    add("fused2/fused_kv_packed",
        lambda k, g, v: _mst.fused2_fused_postscan_reorder_pallas(
            k, g, v, spec=pair, split=4, family="packed"),
        _keys(), _g(256), _keys())

    # radix doors (thin BitfieldSpec wrappers — linted as dispatched)
    add("radix/histograms",
        lambda k: _rp.radix_tile_histograms_pallas(k, 8, 4), _keys())
    add("radix/fused_kv",
        lambda k, g, v: _rp.radix_fused_postscan_reorder_pallas(k, g, v, 8, 4),
        _keys(), _g(_M), _keys())
    add("seg_radix/fused_kv",
        lambda k, s, g, v: _rp.seg_radix_fused_postscan_reorder_pallas(
            k, s, g, v, 8, 4, 2),
        _keys(), _seg(), _g(2 * _M), _keys())

    return ep


@functools.lru_cache(maxsize=1)
def lint_kernels() -> Tuple[LintResult, ...]:
    """Lint every registered entry point; cached (tracing is pure)."""
    return tuple(thunk() for thunk in kernel_entry_points().values())


def lint_report() -> str:
    """Markdown table of per-entry-point lint verdicts (CI step summary)."""
    lines = [
        "| entry point | pallas_calls | verdict | in-kernel primitives |",
        "|---|---|---|---|",
    ]
    for r in lint_kernels():
        verdict = "OK" if r.ok else "FORBIDDEN: " + ", ".join(r.violations)
        lines.append(
            f"| `{r.name}` | {r.pallas_calls} | {verdict} | "
            f"{', '.join(r.kernel_primitives)} |"
        )
    return "\n".join(lines)


def main() -> int:
    results = lint_kernels()
    print(lint_report())
    bad = [r for r in results if not r.ok]
    print()
    print(f"{len(results)} entry points linted, {len(bad)} violations")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
