"""Declarative parameters with logical sharding axes.

Every model parameter is declared as a :class:`ParamDecl` carrying its shape
and a tuple of *logical* axis names. ``logical_to_mesh`` maps logical names
to mesh axes under a :class:`repro.configs.base.ParallelConfig`; from one
declaration tree we derive (a) materialized params, (b) NamedShardings for
pjit, (c) ``ShapeDtypeStruct`` stand-ins for the dry-run — no allocation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ParallelConfig


@dataclasses.dataclass(frozen=True)
class ParamDecl:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]          # logical axis names (None = replicated)
    dtype: Any = jnp.float32
    init: str = "normal"                      # normal | zeros | ones
    scale: Optional[float] = None             # None -> 1/sqrt(fan_in)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_decl(x) -> bool:
    return isinstance(x, ParamDecl)


# Logical axes. "model"-sharded: tensor-parallel dims. "fsdp"-sharded: the
# ZeRO-3 dim (only when ParallelConfig.fsdp). Everything else replicated.
TP_AXES = frozenset({"heads", "kv_heads", "ff", "vocab", "experts", "inner", "state_heads"})
FSDP_AXES = frozenset({"embed", "embed_fsdp"})


def spec_for_decl(decl: ParamDecl, pcfg: ParallelConfig, mesh) -> P:
    """Divisibility-aware logical->mesh assignment.

    jax requires input dims to divide evenly over their mesh axes. When the
    nominated TP dim doesn't divide (e.g. minicpm's 36 heads over model=16,
    GQA kv=8 over 16), the model sharding FALLS BACK to the next dim to the
    right that divides (typically head_dim) — contractions over a sharded
    inner dim become psums under GSPMD, which is correct and usually cheap.
    """
    tp = pcfg.tp_axis if pcfg.tp_axis in mesh.axis_names else None
    tp_size = mesh.shape[tp] if tp else 1
    dp_size = 1
    for a in pcfg.dp_axes:
        dp_size *= mesh.shape[a]
    dp_entry = pcfg.dp_axes if len(pcfg.dp_axes) > 1 else pcfg.dp_axes[0]

    entries = [None] * len(decl.shape)
    # FSDP (ZeRO-3) dims first
    if pcfg.fsdp:
        for i, ax in enumerate(decl.axes):
            if ax in FSDP_AXES and decl.shape[i] % dp_size == 0 and decl.shape[i] >= dp_size:
                entries[i] = dp_entry
                break
    # TP dim: first nominated dim that divides; else fall back rightward
    tp_dims = [i for i, ax in enumerate(decl.axes) if ax in TP_AXES] if tp else []
    if tp_dims:
        placed = False
        for i in tp_dims:
            if entries[i] is None and decl.shape[i] % tp_size == 0 and decl.shape[i] >= tp_size:
                entries[i] = tp
                placed = True
                break
        if not placed:
            for i in range(tp_dims[0] + 1, len(decl.shape)):
                if entries[i] is None and decl.shape[i] % tp_size == 0 and decl.shape[i] >= tp_size:
                    entries[i] = tp
                    break
    return P(*entries)


def decl_to_sharding(decls, pcfg: ParallelConfig, mesh):
    """Declaration tree -> NamedSharding tree (same structure)."""
    return jax.tree.map(
        lambda d: NamedSharding(mesh, spec_for_decl(d, pcfg, mesh)),
        decls,
        is_leaf=is_decl,
    )


def constrain(x, *entries):
    """Divisibility-aware ``with_sharding_constraint`` for activations.

    Entries: "dp" (the data-parallel axes: pod+data), "model", or None.
    No-op outside a mesh context, and per-dim no-op when the dim doesn't
    divide. Used to pin GSPMD's layout for attention and MoE dispatch —
    without these anchors the partitioner sometimes replicates the batch
    dim of 5-D einsums (observed on GQA fallback shardings).
    """
    mesh = jax.sharding.get_abstract_mesh()
    names = getattr(mesh, "axis_names", ()) or ()
    if not names:
        return x
    resolved = []
    for dim, e in enumerate(entries):
        if e is None:
            resolved.append(None)
            continue
        if e == "dp":
            axes = tuple(a for a in names if a in ("pod", "data"))
        elif e == "model":
            axes = ("model",) if "model" in names else ()
        else:
            axes = (e,) if e in names else ()
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if not axes or size <= 1 or x.shape[dim] % size != 0 or x.shape[dim] < size:
            resolved.append(None)
        else:
            resolved.append(axes if len(axes) > 1 else axes[0])
    if all(r is None for r in resolved):
        return x
    return jax.lax.with_sharding_constraint(x, P(*resolved))


def tp_size() -> int:
    mesh = jax.sharding.get_abstract_mesh()
    names = getattr(mesh, "axis_names", ()) or ()
    return mesh.shape["model"] if "model" in names else 1


def decl_to_abstract(decls):
    """Declaration tree -> ShapeDtypeStruct tree (dry-run; no allocation)."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), decls, is_leaf=is_decl
    )


def init_params(decls, rng_key):
    """Materialize a declaration tree (smoke tests / real training only)."""
    leaves, treedef = jax.tree.flatten(decls, is_leaf=is_decl)
    keys = jax.random.split(rng_key, len(leaves))

    def one(decl: ParamDecl, key):
        if decl.init == "zeros":
            return jnp.zeros(decl.shape, decl.dtype)
        if decl.init == "ones":
            return jnp.ones(decl.shape, decl.dtype)
        fan_in = decl.shape[-2] if len(decl.shape) >= 2 else decl.shape[-1]
        scale = decl.scale if decl.scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, decl.shape, jnp.float32) * scale).astype(decl.dtype)

    return jax.tree.unflatten(treedef, [one(d, k) for d, k in zip(leaves, keys)])


def param_count(decls) -> int:
    leaves = jax.tree.leaves(decls, is_leaf=is_decl)
    return sum(int(np.prod(d.shape)) for d in leaves)


def param_bytes(decls) -> int:
    leaves = jax.tree.leaves(decls, is_leaf=is_decl)
    return sum(int(np.prod(d.shape)) * jnp.dtype(d.dtype).itemsize for d in leaves)
