"""Faults planted under the timed path, for the tests that see ``correct``
come out false. Each takes the driver's timed callable and returns a broken
one; the harness is left as it is."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _replace(result, **fields):
    if isinstance(result, tuple) and hasattr(result, "_replace"):
        return result._replace(**fields)
    keys, values = result                      # (keys, values) of a sort
    return (fields.get("keys", keys), fields.get("values", values))


def _field(result, name):
    return getattr(result, name) if hasattr(result, "_replace") else \
        dict(zip(("keys", "values"), result))[name]


def unchanged(call, cfg, mesh):
    """The call hands its input back: keys and values never moved."""
    def broken(keys, values):
        out = call(keys, values)
        fields = {"keys": keys}
        if _field(out, "values") is not None:
            fields["values"] = values
        return _replace(out, **fields)
    return broken


def half_left_out(call, cfg, mesh):
    """The second half of the keys and values is left as it came in."""
    @jax.jit
    def patch(out, keys, values):
        h = keys.shape[0] // 2
        fields = {"keys": _field(out, "keys").at[h:].set(keys[h:])}
        if _field(out, "values") is not None:
            fields["values"] = _field(out, "values").at[h:].set(values[h:])
        return _replace(out, **fields)

    return lambda keys, values: patch(call(keys, values), keys, values)


def answer_altered(call, cfg, mesh):
    """One key of the answer is altered where it is produced."""
    @jax.jit
    def patch(out):
        k = _field(out, "keys")
        return _replace(out, keys=k.at[0].set(k[0] ^ jnp.uint32(1)))

    return lambda keys, values: patch(call(keys, values))


def exchange_left_out(call, cfg, mesh):
    """Each chip partitions its own shard and sends nothing to the others;
    the global bucket counts are still summed, so only the data is wrong."""
    from jax.sharding import PartitionSpec as P

    from repro import ops
    from repro.core.distributed import ShardedMultisplitResult

    spec = ops.delta_buckets(int(cfg["num_buckets"]), key_max=int(cfg["key_max"]))

    def local(keys, values):
        r = ops.multisplit_key_value(keys, values, spec)
        counts = jax.lax.psum(r.bucket_counts, "x")
        return ShardedMultisplitResult(r.keys, r.values, jnp.cumsum(counts) - counts,
                                       counts)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("x"), P("x")),
        out_specs=ShardedMultisplitResult(P("x"), P("x"), P(), P()), check_vma=False))


FAULTS = {"unchanged": unchanged, "half_left_out": half_left_out,
          "answer_altered": answer_altered}
SHARDED_FAULTS = dict(FAULTS, exchange_left_out=exchange_left_out)
