"""Device-level multisplit: the paper's {local, global, local} model lifted
onto a JAX mesh axis (DESIGN.md §2, §7).

Hierarchy (paper §4.4, one more level than the GPU version):

    tile (VMEM direct solve)  ->  chip (grid accumulation)
        ->  device axis (THIS module: one tiny collective + one run per peer)

Key property (paper §4.7 lifted to ICI): after each device *locally
reorders* its shard bucket-major, the map ``local index -> global output
position`` is strictly increasing. Hence the data each device must send to
any given peer is ONE contiguous run of its local buffer — i.e., the local
reorder turns a random inter-device scatter into one contiguous slice per
peer: a padded row of a dense ``all_to_all`` (``multisplit_sharded``) or a
segment of a ``ragged_all_to_all`` (``multisplit_bucket_sharded``), and the
receiver's runs arrive source-major, for a second local multisplit to put
bucket-major. Without the reorder (DMS), per-peer sends are scattered and
the collective degenerates to a dense gather/scatter; this is the paper's
coalescing argument, with "DRAM burst" replaced by "ICI DMA".
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.identifiers import BucketSpec, IdentitySpec
from repro.core.pipeline import MultisplitResult, backend_for, make_plan
from repro.runtime import tracing

Array = jnp.ndarray


def multisplit_all_shards(
    keys: Array,
    bucket_fn: BucketSpec,
    values: Optional[Array] = None,
    *,
    method: str = "bms",
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

    Use this as the single-process path for multi-shard data (verification,
    one-host serving); the collective version below is its
    mesh-distributed twin.
    """
    d_num, n_shard = keys.shape
    plan = make_plan(
        n_shard,
        bucket_fn.num_buckets,
        method=method,
        key_value=values is not None,
        backend=backend_for(backend, n_shard, keys.dtype),
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


def _local_plan(keys: Array, bucket_fn: BucketSpec, values, method: str,
                backend: str, tile):
    """The per-device local stage IS a multisplit plan (DESIGN.md §3/§7):
    the device shard is one subproblem of the same {prescan, scan, postscan}
    pipeline that tiles are — so it is built from the shared plan layer
    instead of re-assembling ``ms.multisplit`` internals."""
    plan = make_plan(
        keys.shape[0],
        bucket_fn.num_buckets,
        method=method,
        key_value=values is not None,
        backend=backend,
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
    """Compute the run transport's plan from the gathered histogram.

    ``hist_all``: (D, m) per-device bucket counts — the paper's matrix H with
    L = D columns. Everything below is O(D·m + D²) scalar work, computed
    redundantly on every device (recompute-over-communicate, paper §5.3).
    Returns the full (D_src, D_dst) matrices of run starts in each source's
    reordered buffer and run lengths, so the caller can slice both its
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


def _count_exchange(shipped: Array, payload: Array) -> None:
    """The exchange counters of one array moved (added once per trace):
    ``exchange_shipped_bytes``, the operand bytes of the transport's
    collectives on this chip, padding included, and
    ``exchange_payload_bytes``, the bytes of the array they carry."""
    tracing.count(exchange_shipped_bytes=int(shipped),
                  exchange_payload_bytes=int(payload))


def _transport_runs(buf, in_off, out_off, axis_name):
    """Dense run transport (XLA:CPU-compilable): no per-element index.

    Row d of the (D, n_dev) send buffer is the slice of the bucket-major
    ``buf`` starting at ``in_off[d]``: this chip's run for peer d, then
    whatever follows it, which the receiver overwrites or cuts. One dense
    ``all_to_all`` ships it; row s of what arrives is source s's run, and
    writing row s at ``out_off[s]`` in source order compacts the runs into
    exactly n_dev elements, source-major, each row overwriting the unused
    tail of the row before. Per chip it ships D·n_dev elements per array.
    """
    n_dev = buf.shape[0]
    d_num = in_off.shape[0]
    padded = jnp.concatenate([buf, jnp.zeros_like(buf)])               # (2 n_dev,)
    send_buf = jnp.stack([jax.lax.dynamic_slice_in_dim(padded, in_off[d], n_dev)
                          for d in range(d_num)])                      # (D, n_dev)
    _count_exchange(send_buf.nbytes, buf.nbytes)
    recv_buf = jax.lax.all_to_all(send_buf, axis_name, split_axis=0, concat_axis=0)
    out = jnp.zeros_like(padded)
    for s in range(d_num):
        out = jax.lax.dynamic_update_slice_in_dim(out, recv_buf[s], out_off[s], axis=0)
    return out[:n_dev]


def multisplit_sharded(
    keys: Array,
    bucket_fn: BucketSpec,
    values: Optional[Array] = None,
    *,
    axis_name: str,
    method: str = "bms",
    backend: Optional[str] = None,
    tile: Optional[int] = None,
    transport: str = "dense",
) -> ShardedMultisplitResult:
    """Exact global stable multisplit across a mesh axis.

    Must be called inside ``shard_map`` over ``axis_name``; ``keys`` is this
    device's equal-size shard. Output: shard ``d`` of the result holds global
    positions ``[d*n_dev, (d+1)*n_dev)`` of the bucket-major output.

    The local stages run ``backend``, else
    :func:`~repro.core.pipeline.default_backend` of the shard (the compiled
    ``pallas`` kernels on a TPU for 32-bit keys). Local -> global -> local,
    as the paper's model: each chip reorders its shard bucket-major, so its data
    for each peer is one contiguous run; ``transport="dense"``, the only
    one, ships the runs with one dense ``all_to_all`` per array (keys, then
    values), each padded to a full shard, and no positions. A chip receives
    exactly n_dev elements, the runs of its sources in source order; a
    second stable local multisplit of them gives the bucket-major shard,
    source-major inside each bucket, which is the global order. A chip thus
    ships D shards of data per array, where about (D-1)/D of one shard
    leaves it.
    """
    if transport != "dense":
        raise ValueError(
            f"multisplit_sharded supports transport='dense' only, got {transport!r}")
    n_dev = keys.shape[0]
    d_num = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    m = bucket_fn.num_buckets
    name = backend_for(backend, n_dev, keys.dtype)

    # ---- local stage: reorder shard bucket-major, get local histogram ----
    local = _local_plan(keys, bucket_fn, values, method, name, tile)

    with tracing.span("repro.stage.exchange", transport=transport, chips=d_num,
                      n_shard=n_dev, m=m, backend=name):
        # ---- global stage: ONE tiny collective over H (D, m) + replicated scan
        hist_all = jax.lax.all_gather(local.bucket_counts, axis_name)    # (D, m)
        in_off_all, send_all, g_flat, totals = _send_plan(hist_all, n_dev)
        recv = send_all[:, my_idx]                                       # (src,), sums to n_dev
        out_off = (jnp.cumsum(recv) - recv).astype(jnp.int32)           # src-major layout

        move = lambda buf: _transport_runs(buf, in_off_all[my_idx], out_off, axis_name)
        keys_rx = move(local.keys)
        values_rx = move(local.values) if values is not None else None

    # ---- final local stage: source-major -> bucket-major (stable) ----
    merged = _local_plan(keys_rx, bucket_fn, values_rx, method, name, tile)
    return ShardedMultisplitResult(merged.keys, merged.values, g_flat,
                                   totals.astype(jnp.int32))


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
    name = backend_for(backend, n_dev, keys.dtype)
    local = _local_plan(keys, bucket_fn, values, method, name, tile)

    with tracing.span("repro.stage.exchange", transport=transport, chips=d_num,
                      n_shard=n_dev, m=m, backend=name):
        hist_all = jax.lax.all_gather(local.bucket_counts, axis_name)  # (D, m)

        group = hist_all.reshape(d_num, d_num, mb)                      # (src, dstgroup, mb)
        send_matrix = group.sum(-1).astype(jnp.int32)                   # (src, dst)
        local_starts = (jnp.cumsum(local.bucket_counts)
                        - local.bucket_counts).astype(jnp.int32)
        in_off = local_starts[jnp.arange(d_num) * mb]                   # (dst,) my run starts
        send = send_matrix[my_idx]                                      # (dst,)
        recv = send_matrix[:, my_idx]                                   # (src,)
        out_off = (jnp.cumsum(recv) - recv).astype(jnp.int32)           # src-major receiver layout
        # ragged_all_to_all wants sender-side knowledge of where its chunk
        # lands on each receiver: cumulative sizes of lower-indexed sources.
        send_out_off = (jnp.cumsum(send_matrix, axis=0) - send_matrix)[my_idx]  # (dst,)

        if transport == "ragged":
            def move(buf):
                _count_exchange(buf.nbytes, buf.nbytes)
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
                _count_exchange(packed.nbytes, buf.nbytes)
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
    sub_ids = jnp.where(valid, sub_ids, mb - 1).astype(jnp.int32)  # pads: last sub-bucket
    dest = make_plan(
        capacity, mb, method=method,
        backend=backend_for(backend, capacity, sub_ids.dtype),
        tile=tile, bucket_fn=IdentitySpec(mb), mode="positions_only",
    )(sub_ids).permutation
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
