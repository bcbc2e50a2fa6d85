"""Drive the system's main path once on a TPU and check every result.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: only the sharded paths

One process, seeded data made on the device, public entry points only:

* multisplit: ``repro.ops.multisplit`` / ``multisplit_key_value`` over
  n = 2^25 uniform uint32 keys (the paper's headline size), m in
  {2, 32, 256} delta buckets, on the compiled ``pallas`` backend and on
  ``vmap``, each against a numpy stable partition by bucket id;
* the default path: ``multisplit_key_value`` (m = 256) and ``radix_sort``
  over the same keys with no backend named, jitted and eager, which must
  resolve to ``pallas`` and run its kernels;
* radix sort of 2^25 keys and key-value pairs, chained (r = 8) and with
  fused digit pairs (r = 4), against ``np.sort`` / a stable ``np.argsort``;
* histogram, m = 256, against ``np.bincount``;
* the routing server: ``ServerLoop(ServingConfig(backend="pallas"))``
  answering open-loop synthetic traffic, every routed step checked against
  the reference backend's routing;
* one MoE layer of dbrx-132b at its published widths on 4096 tokens,
  ``dispatch="multisplit"`` against ``dispatch="sort"`` on the same weights.

Every ``pallas`` program is checked to contain ``tpu_custom_call`` (the
kernels were compiled, not interpreted). Strict mode is on, so no failure
is absorbed by the degradation ladder, and every resilience counter must
stay 0. Any failure exits non-zero without printing a result. The last line
of standard output is one JSON object naming the device.

``--chips 4`` runs ``multisplit_sharded`` (dense all-to-all) with default
arguments, whose local stage must resolve to ``pallas``,
``multisplit_bucket_sharded`` (``ragged_all_to_all``) over 2^25 keys in total
and the expert-parallel MoE dispatch on a 4-device mesh, against the same
one-device references, and checks that every array spans all four devices.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
N = 1 << 25
MOE_TOKENS = 4096
# MoE agreement: max |y - y_ref| <= MOE_TOL * max(1, max |y_ref|). Both
# dispatches route the same tokens to the same slots; bf16 keeps 8 mantissa
# bits and the expert-parallel path sums partial outputs in another order.
MOE_TOL = 2.0 ** -5


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    parts = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[chip_smoke] {phase}: {parts}", flush=True)


def has_kernel(compiled) -> bool:
    """The compiled program calls a Mosaic kernel (not an interpreted one)."""
    return "tpu_custom_call" in compiled.as_text()


def compile_and_run(fn, *args, pallas: bool):
    """AOT-compile ``fn`` for ``args``, run it twice; returns
    (output, compile_s, run_s of the second, warm call)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    if pallas:
        check(has_kernel(compiled),
              "pallas program holds no tpu_custom_call: kernels not compiled")
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, compile_s, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

class Reference:
    """numpy references over one host copy of the keys (values = arange)."""

    def __init__(self, keys_np: np.ndarray):
        self.keys = keys_np
        self._orders = {}

    def bucket_ids(self, m: int) -> np.ndarray:
        delta = max(1, (1 << 32) // m)              # DeltaSpec(m, 2^32)
        return np.minimum(self.keys // np.uint32(delta), m - 1)

    def order(self, m: int) -> np.ndarray:
        if m not in self._orders:
            self._orders[m] = np.argsort(self.bucket_ids(m), kind="stable")
        return self._orders[m]


def phase_multisplit(ops, keys, vals, ref: Reference) -> None:
    for backend in ("pallas", "vmap"):
        for m in (2, 32, 256):
            spec = ops.delta_buckets(m, key_max=1 << 32)
            order = ref.order(m)
            counts = np.bincount(ref.bucket_ids(m), minlength=m)
            for kv in ((False, True) if backend == "pallas" else (True,)):
                if kv:
                    fn = functools.partial(
                        ops.multisplit_key_value, spec=spec, backend=backend)
                    res, c_s, r_s = compile_and_run(
                        fn, keys, vals, pallas=backend == "pallas")
                else:
                    fn = functools.partial(ops.multisplit, spec=spec, backend=backend)
                    res, c_s, r_s = compile_and_run(fn, keys, pallas=True)
                agree = (np.array_equal(np.asarray(res.keys), ref.keys[order])
                         and np.array_equal(np.asarray(res.bucket_counts), counts)
                         and (not kv or np.array_equal(np.asarray(res.values), order)))
                report("multisplit", backend=backend, n=N, m=m, key_value=kv,
                       compile_s=f"{c_s:.2f}", run_s=f"{r_s:.4f}", agree=agree)
                check(agree, f"multisplit {backend} m={m} kv={kv} disagrees with numpy")
                del res


def phase_radix(ops, keys, vals, ref: Reference) -> None:
    want_keys = np.sort(ref.keys, kind="stable")
    want_vals = np.argsort(ref.keys, kind="stable")
    for radix_bits, fuse in ((8, False), (4, True)):
        for kv in (False, True):
            fn = functools.partial(ops.radix_sort, backend="pallas",
                                   radix_bits=radix_bits, fuse_digits=fuse)
            args = (keys, vals) if kv else (keys,)
            (k_out, v_out), c_s, r_s = compile_and_run(fn, *args, pallas=True)
            agree = np.array_equal(np.asarray(k_out), want_keys) and (
                not kv or np.array_equal(np.asarray(v_out), want_vals))
            report("radix_sort", backend="pallas", n=N, radix_bits=radix_bits,
                   fuse_digits=fuse, key_value=kv, compile_s=f"{c_s:.2f}",
                   run_s=f"{r_s:.4f}", agree=agree)
            check(agree, f"radix sort r={radix_bits} fuse={fuse} kv={kv} disagrees")


def phase_default(ops, keys, vals, ref: Reference) -> None:
    import jax

    from repro.core.pipeline import backend_decisions

    m = 256
    order = ref.order(m)
    want_keys = np.sort(ref.keys, kind="stable")
    kv = functools.partial(ops.multisplit_key_value,
                           spec=ops.delta_buckets(m, key_max=1 << 32))
    res, c_s, r_s = compile_and_run(kv, keys, vals, pallas=True)
    agree = (np.array_equal(np.asarray(res.keys), ref.keys[order])
             and np.array_equal(np.asarray(res.values), order))
    report("default", op="multisplit_key_value", n=N, m=m, jit=True,
           compile_s=f"{c_s:.2f}", run_s=f"{r_s:.4f}", agree=agree)
    del res
    for name, fn, args in (("multisplit_key_value", kv, (keys, vals)),
                           ("radix_sort", ops.radix_sort, (keys,))):
        jax.block_until_ready(fn(*args))               # warm-up
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        e_s = time.perf_counter() - t0
        if name == "radix_sort":
            agree = agree and np.array_equal(np.asarray(out[0]), want_keys)
        else:
            agree = (agree and np.array_equal(np.asarray(out.keys), ref.keys[order])
                     and np.array_equal(np.asarray(out.values), order))
        del out
        decision = backend_decisions().get((N, str(keys.dtype)))
        report("default", op=name, n=N, m=m, decision=decision,
               eager_s=f"{e_s:.4f}", agree=agree)
        check(decision == ("pallas", "tpu+32-bit keys"),
              f"default {name} resolved to {decision}, not the compiled kernels")
    check(agree, "the default path disagrees with numpy")


def phase_histogram(ops, keys, ref: Reference) -> None:
    spec = ops.delta_buckets(256, key_max=1 << 32)
    fn = functools.partial(ops.histogram, spec=spec, backend="pallas")
    counts, c_s, r_s = compile_and_run(fn, keys, pallas=True)
    agree = np.array_equal(np.asarray(counts),
                           np.bincount(ref.bucket_ids(256), minlength=256))
    report("histogram", backend="pallas", n=N, m=256, compile_s=f"{c_s:.2f}",
           run_s=f"{r_s:.4f}", agree=agree)
    check(agree, "histogram disagrees with np.bincount")


def phase_server(ops, seed: int, n_requests: int = 400, qps: float = 2000.0) -> None:
    import jax

    from repro.serving import ServerLoop, ServingConfig
    from repro.serving.engine import _routing_op
    from repro.serving.traffic import open_loop, poisson_arrivals, synthetic_requests

    cfg = ServingConfig(backend="pallas")
    loop = ServerLoop(cfg)
    t0 = time.perf_counter()
    loop.prewarm()
    c_s = time.perf_counter() - t0
    ids = np.zeros((cfg.token_pad_classes[-1],), np.int32)
    starts = np.zeros((cfg.max_batch_requests + 1,), np.int32)
    check(has_kernel(loop._jit_step.lower(ids, starts).compile()),
          "serving step holds no tpu_custom_call")
    steps = []
    launch = loop._jit_step

    def recording_launch(ids, starts):
        out = launch(ids, starts)
        steps.append((ids, starts, out))
        return out

    loop._jit_step = recording_launch
    reqs = synthetic_requests(n_requests, cfg.num_experts, seed=seed)
    arrivals = poisson_arrivals(n_requests, qps, seed=seed)
    ops.set_verify(2)
    try:
        t0 = time.perf_counter()
        summary = open_loop(loop, reqs, arrivals)
        r_s = time.perf_counter() - t0
    finally:
        ops.set_verify(0)
    ref_run, _ = _routing_op(cfg.num_experts, cfg.capacity, "reference")
    mismatched = 0
    for ids, starts, out in steps:
        want = ref_run(ids, starts)
        mismatched += not all(np.array_equal(np.asarray(a), np.asarray(b))
                              for a, b in zip(out, want))
    completed = int(summary["completed"])
    agree = (mismatched == 0 and completed == n_requests
             and int(summary["dropped_by_bug"]) == 0 and int(summary["failed"]) == 0)
    report("server", backend="pallas", requests=n_requests, completed=completed,
           steps=len(steps), mismatched_steps=mismatched,
           dropped_by_bug=int(summary["dropped_by_bug"]),
           degradations=int(summary.get("degradations", 0)),
           compile_s=f"{c_s:.2f}", run_s=f"{r_s:.4f}", agree=agree)
    check(agree, "routing server: incomplete requests or steps that disagree "
                 "with the reference routing")
    jax.block_until_ready([o for _, _, o in steps])


def _moe_params(decls, seed: int):
    """Random bf16 weights made on the device, one jitted draw per leaf."""
    import jax
    import jax.numpy as jnp

    from repro.parallel.sharding import is_decl

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def draw(key, shape, scale, init):
        if init == "ones":
            return jnp.ones(shape, jnp.bfloat16)
        if init == "zeros":
            return jnp.zeros(shape, jnp.bfloat16)
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.bfloat16)

    leaves, treedef = jax.tree.flatten(decls, is_leaf=is_decl)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = []
    for d, k in zip(leaves, keys):
        fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
        scale = d.scale if d.scale is not None else float(1.0 / np.sqrt(fan_in))
        out.append(draw(k, tuple(d.shape), float(scale), d.init))
    return jax.tree.unflatten(treedef, out)


def moe_inputs(seed: int, mesh_tokens: int = MOE_TOKENS):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import moe

    cfg = get_config("dbrx-132b")
    params = _moe_params(moe.moe_decl(cfg), seed)
    x = jax.random.normal(jax.random.key(seed + 1), (1, mesh_tokens, cfg.d_model),
                          jnp.bfloat16)
    return cfg, params, x


def moe_fn(cfg, dispatch: str):
    from repro.models import moe

    c = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, dispatch=dispatch))
    return lambda p, x: moe.moe_block(p, x, c)


def moe_agree(got, want) -> tuple:
    (y, aux), (y_ref, aux_ref) = got, want
    y, y_ref = np.asarray(y, np.float32), np.asarray(y_ref, np.float32)
    err = float(np.max(np.abs(y - y_ref)))
    bound = MOE_TOL * max(1.0, float(np.max(np.abs(y_ref))))
    ok = (bool(np.isfinite(y).all()) and err <= bound
          and float(aux.drop_fraction) == float(aux_ref.drop_fraction))
    return ok, err, bound


def phase_moe(seed: int) -> None:
    cfg, params, x = moe_inputs(seed)
    want, c_ref, r_ref = compile_and_run(moe_fn(cfg, "sort"), params, x, pallas=False)
    got, c_s, r_s = compile_and_run(moe_fn(cfg, "multisplit"), params, x, pallas=False)
    ok, err, bound = moe_agree(got, want)
    report("moe", config=cfg.name, tokens=MOE_TOKENS, d_model=cfg.d_model,
           experts=cfg.moe.num_experts, top_k=cfg.moe.top_k, d_ff=cfg.d_ff,
           compile_s=f"{c_s:.2f}", run_s=f"{r_s:.4f}", sort_compile_s=f"{c_ref:.2f}",
           sort_run_s=f"{r_ref:.4f}", max_abs_err=f"{err:.3e}", bound=f"{bound:.3e}",
           agree=ok)
    check(ok, f"MoE multisplit dispatch disagrees with sort (max err {err:.3e})")


def run_one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro import ops

    keys = jax.random.bits(jax.random.key(seed), (N,), jnp.uint32)
    vals = jnp.arange(N, dtype=jnp.int32)
    ref = Reference(np.asarray(keys))
    phase_multisplit(ops, keys, vals, ref)
    phase_radix(ops, keys, vals, ref)
    phase_default(ops, keys, vals, ref)
    phase_histogram(ops, keys, ref)
    del keys, vals, ref
    phase_server(ops, seed)
    phase_moe(seed)


# ---------------------------------------------------------------------------
# Four chips: only the sharded paths
# ---------------------------------------------------------------------------

def _spans(arr, devices) -> bool:
    """The array's shards sit on every device and none holds all of it."""
    shards = arr.addressable_shards
    return ({s.device for s in shards} == set(devices)
            and all(s.data.size < arr.size for s in shards))


def run_four_chips(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import ops
    from repro.core import distributed as dist
    from repro.core.pipeline import backend_decisions

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = jax.make_mesh((4,), ("x",), devices=devices)
    check(mesh.devices.size == 4, "mesh does not span four devices")
    shard = NamedSharding(mesh, P("x"))
    keys = jax.device_put(jax.random.bits(jax.random.key(seed), (N,), jnp.uint32), shard)
    vals = jax.device_put(jnp.arange(N, dtype=jnp.int32), shard)
    check(_spans(keys, devices) and _spans(vals, devices), "inputs not sharded 4 ways")
    ref = Reference(np.asarray(keys))
    m = 256
    spec = ops.delta_buckets(m, key_max=1 << 32)
    order = ref.order(m)

    fn = dist.make_multisplit_sharded(spec, mesh, "x", key_value=True)
    res, c_s, r_s = compile_and_run(fn, keys, vals, pallas=True)
    decision = backend_decisions().get((N // 4, "uint32"))
    agree = (_spans(res.keys, devices)
             and np.array_equal(np.asarray(res.keys), ref.keys[order])
             and np.array_equal(np.asarray(res.values), order))
    report("multisplit_sharded", devices=4, n=N, m=m, transport="dense",
           backend=decision, compile_s=f"{c_s:.2f}", run_s=f"{r_s:.4f}", agree=agree)
    check(decision == ("pallas", "tpu+32-bit keys"),
          f"the sharded local stage did not default to pallas: {decision}")
    check(agree, "multisplit_sharded disagrees with the one-device reference")

    ids = ref.bucket_ids(m)
    group_size = np.bincount(ids // (m // 4), minlength=4)
    capacity = int(group_size.max()) + 1024

    def bucket_sharded(k, v):
        return dist.multisplit_bucket_sharded(
            k, spec, v, axis_name="x", capacity=capacity, transport="ragged",
            backend="pallas")

    fn = jax.shard_map(
        bucket_sharded, mesh=mesh, in_specs=(P("x"), P("x")),
        out_specs=dist.BucketShardedResult(P("x"), P("x"), P("x"), P("x"), P()),
        check_vma=False)
    res, c_s, r_s = compile_and_run(fn, keys, vals, pallas=True)
    got_k = np.asarray(res.keys).reshape(4, capacity)
    got_v = np.asarray(res.values).reshape(4, capacity)
    count = np.asarray(res.count)
    bounds = np.concatenate([[0], np.cumsum(group_size)])
    agree = _spans(res.keys, devices) and np.array_equal(count, group_size)
    for d in range(4):
        sl = order[bounds[d]:bounds[d + 1]]
        agree = agree and np.array_equal(got_k[d, :group_size[d]], ref.keys[sl]) \
            and np.array_equal(got_v[d, :group_size[d]], sl)
    report("multisplit_bucket_sharded", devices=4, n=N, m=m, transport="ragged",
           capacity=capacity, compile_s=f"{c_s:.2f}", run_s=f"{r_s:.4f}", agree=agree)
    check(agree, "multisplit_bucket_sharded disagrees with the one-device reference")
    del keys, vals, res, ref

    cfg, params, x = moe_inputs(seed)
    want, _, _ = compile_and_run(moe_fn(cfg, "sort"), params, x, pallas=False)
    ep_mesh = jax.make_mesh((1, 4), ("data", "model"), devices=devices)
    w_shard = NamedSharding(ep_mesh, P("model", None, None))
    params_ep = {k: (jax.device_put(v, w_shard) if k.startswith("w_") else v)
                 for k, v in params.items()}
    check(all(_spans(params_ep[k], devices) for k in ("w_gate", "w_up", "w_down")),
          "expert weights not sharded over the model axis")
    with jax.set_mesh(ep_mesh):
        got, c_s, r_s = compile_and_run(moe_fn(cfg, "multisplit_ep"), params_ep, x,
                                        pallas=False)
    ok, err, bound = moe_agree(got, want)
    report("moe_multisplit_ep", devices=4, config=cfg.name, tokens=MOE_TOKENS,
           compile_s=f"{c_s:.2f}", run_s=f"{r_s:.4f}", max_abs_err=f"{err:.3e}",
           bound=f"{bound:.3e}", agree=ok)
    check(ok, f"expert-parallel MoE disagrees with sort on one device ({err:.3e})")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_INTERPRET"):
        print("chip_smoke: REPRO_INTERPRET is set; the smoke runs compiled "
              "kernels only", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.cache import enable_compile_cache
    except ImportError:
        print("chip_smoke: the repro package is not beside this script "
              f"(looked in {os.path.join(ROOT, 'src')})", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices); "
              "the smoke never runs on another platform", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    from repro import ops
    from repro.runtime import resilience as rz

    ops.set_strict(True)
    rz.reset_stats()
    print(f"[chip_smoke] device={dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__} cache={cache_dir}", flush=True)
    try:
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
        stats = rz.stats()
        bad = {k: stats[k] for k in ("degradations", "backend_demotions", "tile_shrinks",
                                     "reference_reruns", "verify_mismatches") if stats[k]}
        report("resilience", **{k: stats[k] for k in sorted(stats)})
        check(not bad, f"resilience counters moved: {bad}")
    except Exception:  # noqa: BLE001 - any failure fails the smoke
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
