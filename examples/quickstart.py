"""Quickstart: the transform-native multisplit API (`repro.ops`) in 40 lines.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro import ops

# --- 1. multisplit 256K keys into 32 equal-width buckets (paper §6 setup) ---
# Specs are declarative, HASHABLE values: equal specs share one jit trace,
# and on kernel backends their bucket function is evaluated in-register
# inside the tile kernels (no label array ever exists).
keys = jnp.asarray(np.random.RandomState(0).randint(0, 2**30, 1 << 18, dtype=np.uint32))
values = jnp.arange(keys.shape[0], dtype=jnp.int32)           # payload
spec = ops.delta_buckets(32, 2**30)

out = ops.multisplit(keys, spec, values, method="bms")        # {local, global, local}
print(f"bucket starts: {np.asarray(out.bucket_starts)[:6]} ...")
print(f"bucket counts: {np.asarray(out.bucket_counts)[:6]} ...")
assert bool((jnp.diff(spec(out.keys)) >= 0).all()), "bucket-contiguous"

# --- 2. spec zoo: splitters, radix digits, user escape hatch ----------------
splitters = ops.range_buckets([1 << 20, 1 << 25, 1 << 28])    # sample-sort style
print(f"range{splitters.num_buckets} counts:",
      np.asarray(ops.histogram(keys, splitters)))
parity = ops.from_fn(lambda u: (u & 1).astype(jnp.int32), 2, name="parity")
evens_first = ops.multisplit(keys, parity)                    # CallableSpec: escape hatch
print(f"evens: {int(evens_first.bucket_counts[0])}, odds: {int(evens_first.bucket_counts[1])}")

# --- 3. transforms are first-class ------------------------------------------
# vmap: one BATCHED plan launch for the whole stack (bitwise == per-row loop)
stack = keys[: 8 * 4096].reshape(8, 4096)
per_row_counts = jax.vmap(lambda k: ops.multisplit(k, spec).bucket_counts)(stack)
print(f"vmap'd counts shape: {per_row_counts.shape}")         # (8, 32)

# grad: the key-value multisplit is differentiable in the values — backward
# is the inverse gather of the forward permutation
v = jnp.asarray(np.random.RandomState(1).rand(4096).astype(np.float32))
loss = lambda v: (ops.multisplit_key_value(keys[:4096], v, spec).values ** 2).sum()
g = jax.grad(loss)(v)
assert bool(jnp.allclose(g, 2 * v)), "permutation-equivariant gradient"
print(f"grad through multisplit OK (|g| = {float(jnp.linalg.norm(g)):.2f})")

# --- 4. multisplit-based radix sort (paper §7.1) ----------------------------
# = chained BitfieldSpec passes, digits extracted inside the kernels
sorted_keys, sorted_vals = ops.radix_sort(keys, values, radix_bits=8)
assert bool((sorted_keys[1:] >= sorted_keys[:-1]).all())
print(f"radix sort OK: first keys {np.asarray(sorted_keys[:4])}")

# --- 5. segmented routing: many ragged multisplits in ONE call --------------
# Four "requests" of different sizes share one flat buffer; each is bucketed
# independently (per-request counts, per-request stability) in one launch —
# the building block for batched serving (DESIGN.md §9).
segment_starts = jnp.asarray([0, 50_000, 50_000, 180_000], jnp.int32)  # one empty
seg = ops.segmented_multisplit(keys, spec, segment_starts, values)
print(f"per-request bucket counts, shape {seg.bucket_counts.shape}:")
print(f"  request 0 -> {np.asarray(seg.bucket_counts[0, :4])} ...")
print(f"  request 1 (empty) -> {np.asarray(seg.bucket_counts[1, :4])} ...")
assert int(seg.bucket_counts.sum()) == keys.shape[0]
ids0 = spec(seg.keys[:50_000])
assert bool((jnp.diff(ids0) >= 0).all()), "request 0 bucket-contiguous"

# --- 6. partial pipelines (paper §7.3): counts_only / positions_only --------
h = ops.histogram(keys.astype(jnp.float32), ops.even_buckets(0.0, float(2**30), 64))
print(f"histogram (64 even bins): min {int(h.min())}, max {int(h.max())}")
counts = ops.multisplit(keys, spec, mode="counts_only").bucket_counts
assert bool((counts == out.bucket_counts).all()), "counts_only == full pipeline"
ranks = ops.multisplit(keys, spec, mode="positions_only").permutation
assert int(ranks.shape[0]) == keys.shape[0]

# --- 7. kernel families + autotuning (DESIGN.md §12) ------------------------
# Wide bucket axes auto-select the PACKED subword-counter family (bitwise
# identical to the dense one-hot family, ~flat per-key cost in m); the
# decision — and WHY it was made — is inspectable, and `autotune_tile`
# searches the (tile, family) grid jointly and pins the measured winner.
from repro.core.pipeline import autotune_tile, family_decision, make_plan

wide = make_plan(keys.shape[0], 256, bucket_fn=ops.delta_buckets(256, 2**30))
fam, why = family_decision(keys.shape[0], 256, "bms", "vmap")
print(f"m=256 plan: family={wide.family!r}, tile={wide.tile} ({why})")
tuned = autotune_tile(1 << 14, ops.delta_buckets(256, 2**30),
                      candidates=(1024, 4096), trials=1)
print(f"autotuned (tile, family) for m=256: "
      f"({tuned}, {family_decision(1 << 14, 256, 'bms', 'vmap')[0]!r})")

# --- 8. fused digit pairs (DESIGN.md §13) -----------------------------------
# fuse_digits=True sorts TWO radix digits per HBM round-trip: each tile is
# loaded into VMEM once and multisplit over the combined 2r-bit digit, so a
# 32-bit r=8 sort runs 2 sweeps instead of 4 (~2x chained on the host bench).
# LSD stability makes the fused result bitwise identical to the chained one.
fused_keys, fused_vals = ops.radix_sort(keys, values, radix_bits=8,
                                        fuse_digits=True)
assert bool((fused_keys == sorted_keys).all()), "fused == chained, bitwise"
assert bool((fused_vals == sorted_vals).all())
from repro.core.pipeline import RadixPipeline

pipe = RadixPipeline(keys.shape[0], radix_bits=8, backend="vmap",
                     fuse_digits=True)
print(f"fused r=8 sort: {pipe.n_sweeps} sweeps for {pipe.n_passes} digits, "
      f"stage 0 = {pipe.plans[0].stages()[0]!r}")

# --- 9. self-tuning (DESIGN.md §14) -----------------------------------------
# Opt in and every per-shape decision (tile, family, fused-pair sub_bits,
# vmap label fusion) resolves by MEASUREMENT on first miss, persisting the
# winners per host (~/.cache/repro-multisplit by default, or
# set_autotune(cache_dir=...)) — the second process pays zero search time.
# Everything stays heuristic until you arm it; REPRO_AUTOTUNE=1 works too.
import tempfile

from repro.core.pipeline import clear_tile_cache

with tempfile.TemporaryDirectory() as d:                # demo: throwaway cache
    ops.set_autotune(True, cache_dir=d, trials=1, candidates=(1024, 4096))
    clear_tile_cache()                                  # force fresh misses
    tuned_plan = make_plan(1 << 14, 256, bucket_fn=ops.delta_buckets(256, 2**30))
    fam, why = family_decision(1 << 14, 256, "bms", "vmap")
    print(f"self-tuned plan: tile={tuned_plan.tile}, family={fam!r}")
    print(f"  reason: {why[:72]}...")
    ops.set_autotune(False)
    clear_tile_cache()

# --- 10. the compiled kernel path (DESIGN.md §15) ---------------------------
# backend="pallas" means COMPILED-WHEN-AVAILABLE: on a TPU host the kernels
# lower under Mosaic (interpret=False) — every body is gather/scatter-free
# by construction (linted: python -m repro.kernels.lint) — and on a
# TPU-less host the same plans fall back to the interpreter automatically.
# backend="pallas-interpret" stays pinned to the interpreter (the debug
# target). Override either way per process with the environment variable:
#   REPRO_INTERPRET=1   force interpretation everywhere (debug on TPU)
#   REPRO_INTERPRET=0   force compiled lowering (e.g. CPU Mosaic tests)
from repro.core.pipeline import get_backend

b = get_backend("pallas")
print(f"pallas: compiled={b.compiled}, interpret-now={b.stages.interpret}")

# --- 11. request-level serving (DESIGN.md §16) ------------------------------
# Continuous batching: many concurrent users' token streams coalesce into
# ONE segmented plan launch per step (admission by RangeSpec length
# bucketing, warm-plan reuse, bounded fault retry, load shedding). The
# exported metrics are exact nearest-rank percentiles + sustained QPS —
# CLI: PYTHONPATH=src python -m repro.launch.serve --traffic
from repro.serving import ServerLoop, ServingConfig

loop = ServerLoop(ServingConfig(num_experts=8, capacity=16,
                                max_batch_requests=16, max_batch_tokens=256))
loop.prewarm()                              # compile every shape class now
rng = np.random.RandomState(0)
for n_tok in (5, 0, 17, 3, 9, 12):          # ragged streams, one idle user
    loop.submit(rng.randint(0, 8, size=n_tok).astype(np.int32))
served = loop.drain()                       # graceful flush + final metrics
print(f"serving: completed={served['completed']:.0f} in "
      f"{served['steps']:.0f} step(s), p99="
      f"{served['latency_p99_ms']:.2f}ms, "
      f"occupancy={served['batch_token_occupancy']:.2f}, "
      f"dropped_by_bug={served['dropped_by_bug']:.0f}")
assert served["dropped_by_bug"] == 0        # conservation: always

# --- 12. resilience: degradation ladder + runtime verification (§17) --------
# On real hardware a kernel can fail to lower, run out of VMEM, or answer
# wrong. The dispatch layer classifies failures and degrades gracefully:
# transient -> retry in place; resource -> halve the tile (pinning the
# survivor); persistent -> demote pallas -> pallas-interpret -> vmap ->
# reference, with a persistent circuit breaker quarantining plan classes
# that keep failing. Opt-in runtime verification re-checks outputs against
# the paper's invariants and recovers via the reference oracle on mismatch:
#   REPRO_VERIFY=1   counts conservation + offset monotonicity (O(m))
#   REPRO_VERIFY=2   + true-permutation / bucket-order proof (O(n log n))
#   REPRO_STRICT=1   disable ALL fallback: fail loud with the original error
ops.set_verify(2)                           # or REPRO_VERIFY=2 per process
verified = ops.multisplit(keys, spec, backend="pallas")
ops.set_verify(None)
from repro.runtime import resilience

counters = {k: v for k, v in resilience.stats().items() if v}
print(f"resilience: verified launch OK, counters={counters or '{}'}")
assert resilience.stats()["verify_mismatches"] == 0

print("quickstart OK")
