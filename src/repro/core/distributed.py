"""Device-level multisplit: the paper's {local, global, local} model lifted
onto a JAX mesh axis (DESIGN.md §2, §7).

Hierarchy (paper §4.4, one more level than the GPU version):

    tile (VMEM direct solve)  ->  chip (grid accumulation)
        ->  device axis (THIS module: one tiny collective + ragged a2a)

Key property (paper §4.7 lifted to ICI): after each device *locally
reorders* its shard bucket-major, the map ``local index -> global output
position`` is strictly increasing. Hence the data each device must send to
any given peer is ONE contiguous run of its local buffer — i.e., the local
reorder turns a random inter-device scatter into a single-segment
``ragged_all_to_all``. Without the reorder (DMS), per-peer sends are
scattered and the collective degenerates to a dense gather/scatter; this is
the paper's coalescing argument, with "DRAM burst" replaced by "ICI DMA".
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.identifiers import BucketSpec, IdentitySpec
from repro.core.pipeline import MultisplitResult, make_plan, resolve_backend

Array = jnp.ndarray


def multisplit_all_shards(
    keys: Array,
    bucket_fn: BucketSpec,
    values: Optional[Array] = None,
    *,
    method: str = "bms",
    use_pallas: bool = False,
    backend: Optional[str] = None,
    tile: Optional[int] = None,
) -> MultisplitResult:
    """The device-level pipeline with the LOCAL stage as ONE batched plan.

    ``keys`` is the (D, n_shard) stack of all shards. Stage 1 runs every
    shard's bucket-major reorder + histogram in a single batched plan launch
    (DESIGN.md §9) — the host-side analogue of ``multisplit_sharded``'s
    per-device local stage, with the D-way host loop (or D separate plan
    calls) collapsed into one grid. Stage 2 is the closed-form global scan
    over the (D, m) histogram matrix H — the same math ``_send_plan``
    computes from the all-gathered H, evaluated directly since every shard
    is host-visible here. Output is the global stable bucket-major
    multisplit of the concatenated shards (bitwise identical to
    ``multisplit_ref`` on ``keys.reshape(-1)``), with the element-ordered
    permutation in flat global coordinates.

    Use this as the single-process path for multi-shard data (benchmarks,
    verification, one-host serving); the collective version below is its
    mesh-distributed twin.
    """
    d_num, n_shard = keys.shape
    plan = make_plan(
        n_shard,
        bucket_fn.num_buckets,
        method=method,
        key_value=values is not None,
        backend=resolve_backend(use_pallas, True, backend),
        tile=tile,
        bucket_fn=bucket_fn,
        batch=d_num,
    )
    local = plan(keys, values)                               # ONE launch, D shards
    hist = local.bucket_counts                               # (D, m) == H
    totals = hist.sum(axis=0).astype(jnp.int32)              # (m,)
    g_flat = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(totals)[:-1].astype(jnp.int32)]
    )
    c_excl = (jnp.cumsum(hist, axis=0) - hist).astype(jnp.int32)     # (D, m)

    # Reordered local slot j of shard d -> global position: the local buffer
    # is bucket-major, so bucket-of-slot comes from the local histogram and
    # the map is strictly increasing per (shard, bucket) run (paper §4.7).
    lidx = jnp.arange(n_shard, dtype=jnp.int32)
    lids = jax.vmap(
        lambda c: jnp.searchsorted(c, lidx, side="right").astype(jnp.int32)
    )(jnp.cumsum(hist, axis=1))                              # (D, n_shard)
    rank = lidx[None, :] - jnp.take_along_axis(local.bucket_starts, lids, axis=1)
    pos = g_flat[lids] + jnp.take_along_axis(c_excl, lids, axis=1) + rank

    n_total = d_num * n_shard
    keys_out = jnp.zeros((n_total,), keys.dtype).at[pos.reshape(-1)].set(
        local.keys.reshape(-1)
    )
    values_out = None
    if values is not None:
        values_out = jnp.zeros((n_total,), values.dtype).at[pos.reshape(-1)].set(
            local.values.reshape(-1)
        )

    # element-ordered permutation of the ORIGINAL (D, n_shard) input
    ids = bucket_fn(keys)                                    # (D, n_shard)
    rank_in = local.permutation - jnp.take_along_axis(local.bucket_starts, ids, axis=1)
    perm = g_flat[ids] + jnp.take_along_axis(c_excl, ids, axis=1) + rank_in

    return MultisplitResult(keys_out, values_out, g_flat, totals, perm.reshape(-1))


def _local_plan(
    keys: Array,
    bucket_fn: BucketSpec,
    values,
    method: str,
    use_pallas: bool,
    backend,
    tile,
):
    """The per-device local stage IS a multisplit plan (DESIGN.md §3/§7):
    the device shard is one subproblem of the same {prescan, scan, postscan}
    pipeline that tiles are — so it is built from the shared plan layer
    instead of re-assembling ``ms.multisplit`` internals."""
    plan = make_plan(
        keys.shape[0],
        bucket_fn.num_buckets,
        method=method,
        key_value=values is not None,
        backend=resolve_backend(use_pallas, True, backend),
        tile=tile,
        bucket_fn=bucket_fn,
    )
    return plan(keys, values)


class ShardedMultisplitResult(NamedTuple):
    keys: Array                 # this device's shard of the global bucket-major output
    values: Optional[Array]
    bucket_starts: Array        # (m,) GLOBAL bucket start positions (replicated)
    bucket_counts: Array        # (m,) GLOBAL histogram (replicated)


def _send_plan(hist_all: Array, n_dev: int):
    """Compute the ragged_all_to_all plan from the gathered histogram.

    ``hist_all``: (D, m) per-device bucket counts — the paper's matrix H with
    L = D columns. Everything below is O(D·m + D²) scalar work, computed
    redundantly on every device (recompute-over-communicate, paper §5.3).
    Returns the full (D_src, D_dst) matrices so caller can slice both its
    sender row and its receiver column.
    """
    d_num, m = hist_all.shape
    totals = hist_all.sum(axis=0)                            # (m,)
    g_flat = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(totals)[:-1].astype(jnp.int32)])
    # C[b, s]: count of bucket b on devices < s  (exclusive scan over devices)
    c_excl = jnp.cumsum(hist_all, axis=0) - hist_all         # (D, m)
    run_start = g_flat[None, :] + c_excl                     # (D, m) global start of (s, b) run
    run_len = hist_all                                       # (D, m)

    # count of device s's elements with global position < X, per boundary X
    bounds = jnp.arange(d_num + 1, dtype=jnp.int32) * n_dev  # (D+1,)
    below = jnp.clip(
        bounds[None, :, None] - run_start[:, None, :], 0, run_len[:, None, :]
    ).sum(-1)                                                # (D, D+1)
    send_matrix = (below[:, 1:] - below[:, :-1]).astype(jnp.int32)   # (D_src, D_dst)
    input_offsets_all = below[:, :-1].astype(jnp.int32)              # (D_src, D_dst)
    return input_offsets_all, send_matrix, g_flat, totals


def _expand(mask, ndim):
    return mask.reshape(mask.shape + (1,) * (ndim - 1))


def _transport_dense_positions(buf, positions, in_off, send, axis_name):
    """Position-carrying dense transport (XLA:CPU-compilable fallback).

    Each source's run for destination d is one contiguous local segment
    (guaranteed by the local reorder); we pad each segment to the shard size,
    ship (data, global position) with a dense ``all_to_all``, and the
    receiver scatters by position. Correct for any interleaving at the
    destination — used on CPU and as the DMS (no-ragged-possible) baseline.
    """
    n_dev = buf.shape[0]
    d_num = send.shape[0]
    idx = jnp.arange(n_dev, dtype=jnp.int32)
    gidx = jnp.clip(in_off[:, None] + idx[None, :], 0, n_dev - 1)      # (D, n_dev)
    send_mask = idx[None, :] < send[:, None]

    def pack(x, fill):
        g = x[gidx.reshape(-1)].reshape((d_num, n_dev) + x.shape[1:])
        return jnp.where(_expand(send_mask, x.ndim), g, fill)

    send_buf = pack(buf, 0)
    send_pos = pack(positions, -1)
    recv_buf = jax.lax.all_to_all(send_buf, axis_name, split_axis=0, concat_axis=0)
    recv_pos = jax.lax.all_to_all(send_pos, axis_name, split_axis=0, concat_axis=0)
    my_idx = jax.lax.axis_index(axis_name)
    local_pos = recv_pos.reshape(-1) - my_idx * n_dev
    local_pos = jnp.where(recv_pos.reshape(-1) < 0, n_dev, local_pos)  # pads -> dropped
    out = jnp.zeros((n_dev,) + buf.shape[1:], buf.dtype)
    return out.at[local_pos].set(recv_buf.reshape((-1,) + buf.shape[1:]), mode="drop")


def multisplit_sharded(
    keys: Array,
    bucket_fn: BucketSpec,
    values: Optional[Array] = None,
    *,
    axis_name: str,
    method: str = "bms",
    use_pallas: bool = False,
    backend: Optional[str] = None,
    tile: Optional[int] = None,
    transport: str = "dense",
) -> ShardedMultisplitResult:
    """Exact global stable multisplit across a mesh axis.

    Must be called inside ``shard_map`` over ``axis_name``; ``keys`` is this
    device's equal-size shard. Output: shard ``d`` of the result holds global
    positions ``[d*n_dev, (d+1)*n_dev)`` of the bucket-major output.

    ``transport="dense"`` ships (data, position) pairs with a padded dense
    ``all_to_all`` (XLA:CPU-compilable). ``transport="ragged"`` (TPU target)
    composes two single-segment ``ragged_all_to_all`` hops: a bucket-sharded
    hop (see :func:`multisplit_bucket_sharded`) followed by an equal-shard
    rebalance — each hop's per-peer payload is one contiguous run, which is
    exactly the paper's reorder-for-coalescing property lifted to ICI.
    """
    n_dev = keys.shape[0]
    my_idx = jax.lax.axis_index(axis_name)

    # ---- local stage: reorder shard bucket-major, get local histogram ----
    local = _local_plan(keys, bucket_fn, values, method, use_pallas, backend, tile)

    # ---- global stage: ONE tiny collective over H (D, m) + replicated scan ----
    hist_all = jax.lax.all_gather(local.bucket_counts, axis_name)    # (D, m)
    in_off_all, send_all, g_flat, totals = _send_plan(hist_all, n_dev)
    in_off = in_off_all[my_idx]
    send = send_all[my_idx]

    # global output position of each local (reordered) element: strictly
    # increasing in local index (bucket-major local x bucket-major global)
    m = bucket_fn.num_buckets
    local_starts = jnp.cumsum(local.bucket_counts) - local.bucket_counts   # (m,)
    c_excl = (jnp.cumsum(hist_all, axis=0) - hist_all)[my_idx]             # (m,)
    lidx = jnp.arange(n_dev, dtype=jnp.int32)
    lids = jnp.searchsorted(jnp.cumsum(local.bucket_counts), lidx, side="right").astype(jnp.int32)
    rank_in_bucket = lidx - local_starts[lids]
    positions = g_flat[lids] + c_excl[lids] + rank_in_bucket               # (n_dev,)

    move = lambda buf: _transport_dense_positions(buf, positions, in_off, send, axis_name)
    keys_out = move(local.keys)
    values_out = move(local.values) if values is not None else None
    return ShardedMultisplitResult(keys_out, values_out, g_flat, totals.astype(jnp.int32))


class BucketShardedResult(NamedTuple):
    keys: Array                 # (capacity,) this device's bucket-group elements, bucket-major
    values: Optional[Array]
    count: Array                # (1,) number of valid elements in this shard
    group_counts: Array         # (m/D,) per-bucket counts within my group
    bucket_counts: Array        # (m,) GLOBAL histogram (replicated)


def multisplit_bucket_sharded(
    keys: Array,
    bucket_fn: BucketSpec,
    values: Optional[Array] = None,
    *,
    axis_name: str,
    capacity: int,
    method: str = "bms",
    use_pallas: bool = False,
    backend: Optional[str] = None,
    tile: Optional[int] = None,
    transport: str = "dense",
) -> BucketShardedResult:
    """Bucket-sharded multisplit: device ``d`` receives all elements of
    buckets ``[d*m/D, (d+1)*m/D)``, bucket-major, padded to ``capacity``.

    This is the MoE expert-dispatch layout. Per (src, dst) peer the payload
    is ONE contiguous run of the source's reordered buffer AND one contiguous
    run of the receiver's buffer (src-major layout) — so the TPU transport is
    a single ``ragged_all_to_all``. A final LOCAL multisplit restores
    bucket-major order: local -> global -> local, the paper's model verbatim.

    Elements beyond ``capacity`` are dropped (standard MoE semantics);
    ``count`` reports the true load so callers can monitor drops.
    """
    d_num = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    m = bucket_fn.num_buckets
    if m % d_num != 0:
        raise ValueError(f"num_buckets {m} must divide over axis size {d_num}")
    mb = m // d_num
    n_dev = keys.shape[0]

    # local stage
    local = _local_plan(keys, bucket_fn, values, method, use_pallas, backend, tile)
    hist_all = jax.lax.all_gather(local.bucket_counts, axis_name)      # (D, m)

    group = hist_all.reshape(d_num, d_num, mb)                          # (src, dstgroup, mb)
    send_matrix = group.sum(-1).astype(jnp.int32)                       # (src, dst)
    local_starts = (jnp.cumsum(local.bucket_counts) - local.bucket_counts).astype(jnp.int32)
    in_off = local_starts[jnp.arange(d_num) * mb]                       # (dst,) my run starts
    send = send_matrix[my_idx]                                          # (dst,)
    recv = send_matrix[:, my_idx]                                       # (src,)
    out_off = (jnp.cumsum(recv) - recv).astype(jnp.int32)               # src-major receiver layout
    # ragged_all_to_all wants sender-side knowledge of where its chunk lands
    # on each receiver: cumulative sizes of lower-indexed sources there.
    send_out_off = (jnp.cumsum(send_matrix, axis=0) - send_matrix)[my_idx]  # (dst,)

    if transport == "ragged":
        def move(buf):
            out = jnp.zeros((capacity,) + buf.shape[1:], buf.dtype)
            return jax.lax.ragged_all_to_all(
                buf, out, in_off, send, send_out_off, recv, axis_name=axis_name
            )
    else:
        def move(buf):
            idx = jnp.arange(n_dev, dtype=jnp.int32)
            gidx = jnp.clip(in_off[:, None] + idx[None, :], 0, n_dev - 1)
            mask = idx[None, :] < send[:, None]
            packed = jnp.where(
                _expand(mask, buf.ndim),
                buf[gidx.reshape(-1)].reshape((d_num, n_dev) + buf.shape[1:]),
                0,
            )
            recv_buf = jax.lax.all_to_all(packed, axis_name, split_axis=0, concat_axis=0)
            recv_buf = recv_buf.reshape((d_num, n_dev) + buf.shape[1:])
            pos = out_off[:, None] + idx[None, :]
            pos = jnp.where(idx[None, :] < recv[:, None], pos, capacity)  # pads dropped
            out = jnp.zeros((capacity,) + buf.shape[1:], buf.dtype)
            return out.at[jnp.clip(pos, 0, capacity).reshape(-1)].set(
                recv_buf.reshape((-1,) + buf.shape[1:]), mode="drop"
            )

    keys_rx = move(local.keys)
    vals_rx = move(local.values) if values is not None else None

    # final local stage: src-major -> bucket-major within my group.
    # Received buffer is a concatenation of per-src bucket-major chunks; a
    # local multisplit on (bucket id within group) restores global order —
    # a positions-only plan, tiled like the first local stage (one dense
    # capacity x m/D one-hot would not fit a chip at n = 2^25).
    lo = my_idx * mb
    sub_ids = jnp.clip(bucket_fn(keys_rx) - lo, 0, mb - 1)
    valid = jnp.arange(capacity) < jnp.minimum(recv.sum(), capacity)
    sub_ids = jnp.where(valid, sub_ids, mb - 1)  # pads ride in the last sub-bucket
    dest = make_plan(
        capacity, mb, method=method, backend=resolve_backend(use_pallas, True, backend),
        tile=tile, bucket_fn=IdentitySpec(mb), mode="positions_only",
    )(sub_ids.astype(jnp.int32)).permutation
    keys_out = jnp.zeros_like(keys_rx).at[dest].set(keys_rx)
    vals_out = None
    if vals_rx is not None:
        vals_out = jnp.zeros_like(vals_rx).at[dest].set(vals_rx)

    group_counts = hist_all.sum(0).reshape(d_num, mb)[my_idx].astype(jnp.int32)
    return BucketShardedResult(
        keys_out, vals_out, jnp.minimum(recv.sum(), capacity)[None],
        group_counts, hist_all.sum(0).astype(jnp.int32),
    )


def make_multisplit_sharded(
    bucket_fn: BucketSpec, mesh, axis_name: str, key_value: bool = False, **kw
):
    """Convenience: wrap ``multisplit_sharded`` in shard_map over one axis."""
    from jax.sharding import PartitionSpec as P

    if key_value:
        def fn(keys, values):
            return multisplit_sharded(keys, bucket_fn, values, axis_name=axis_name, **kw)

        in_specs = (P(axis_name), P(axis_name))
    else:
        def fn(keys):
            return multisplit_sharded(keys, bucket_fn, axis_name=axis_name, **kw)

        in_specs = (P(axis_name),)

    out_specs = ShardedMultisplitResult(
        P(axis_name), P(axis_name) if key_value else None, P(), P()
    )
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
