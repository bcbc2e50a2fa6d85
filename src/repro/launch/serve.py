"""Serving launcher: two entry points behind one CLI.

Batched incremental decoding with a KV/state cache (the model demo)::

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --batch 4 --prompt-len 32 --gen-len 32

``--smoke`` runs the reduced config on the host devices. Prompts are
consumed through the decode path (single-token steps), then generation
continues greedily — one jitted ``decode_step``, shapes static throughout.

Continuous-batching traffic over the segmented routing plan (DESIGN.md
§16) — many concurrent synthetic users coalesced into ONE segmented
multisplit launch per step::

    PYTHONPATH=src python -m repro.launch.serve --traffic \
        --requests 5000 --qps 2000 --fault-rate 0.01

Open-loop Poisson arrivals drive a :class:`repro.serving.ServerLoop`;
the run prints the exported metrics (p50/p95/p99 latency, sustained QPS,
occupancy, shed/failed/retry counters) and conservation-checks that no
request was silently dropped.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_device_mesh, make_host_mesh
from repro.models import model as M
from repro.parallel.sharding import init_params, param_count


def run_traffic(args) -> dict:
    """The continuous-batching path: open-loop Poisson traffic through a
    prewarmed :class:`~repro.serving.ServerLoop` (ONE segmented plan launch
    per step), with optional seeded fault injection exercising the
    retry/requeue/shed machinery under load."""
    from repro.runtime.supervisor import FaultInjector
    from repro.serving import (
        ServerLoop, ServingConfig, open_loop, poisson_arrivals,
        synthetic_requests,
    )

    cfg = ServingConfig(
        num_experts=args.num_experts,
        capacity=args.capacity,
        max_batch_requests=args.max_batch_requests,
        max_batch_tokens=args.max_batch_tokens,
        max_wait=args.max_wait,
        backend=args.backend,
    )
    faults = None
    if args.fault_rate:
        faults = FaultInjector(rate=args.fault_rate, seed=args.seed)
    loop = ServerLoop(cfg, fault_injector=faults)
    t0 = time.monotonic()
    loop.prewarm()
    print(f"[serve] prewarm {time.monotonic() - t0:.2f}s "
          f"(shape classes {cfg.token_pad_classes}, backend {cfg.backend})")

    reqs = synthetic_requests(args.requests, cfg.num_experts, seed=args.seed)
    arrivals = poisson_arrivals(args.requests, args.qps, seed=args.seed)
    print(f"[serve] open loop: {args.requests} requests at {args.qps:.0f} QPS "
          f"(Poisson), fault rate {args.fault_rate}")
    open_loop(loop, reqs, arrivals)

    s = loop.metrics_summary()
    assert s["dropped_by_bug"] == 0, f"request accounting violated: {s}"
    print(f"[serve] completed {s['completed']}/{s['submitted']} "
          f"(shed {s['shed']}, failed {s['failed']}, retries {s['retries']})")
    print(f"[serve] latency ms: p50 {s['latency_p50_ms']:.2f}  "
          f"p95 {s['latency_p95_ms']:.2f}  p99 {s['latency_p99_ms']:.2f}")
    print(f"[serve] sustained {s['qps_sustained']:.0f} QPS over {s['steps']} steps, "
          f"occupancy {s['batch_token_occupancy']:.2f}, "
          f"mean batch {s['batch_requests_mean']:.1f} requests")
    return s


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="model arch for the decode demo (required unless --traffic)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    # continuous-batching traffic mode (DESIGN.md §16)
    ap.add_argument("--traffic", action="store_true",
                    help="serve synthetic open-loop traffic through the "
                         "continuous-batching ServerLoop instead of the decode demo")
    ap.add_argument("--requests", type=int, default=5000)
    ap.add_argument("--qps", type=float, default=2000.0)
    ap.add_argument("--num-experts", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--max-batch-requests", type=int, default=64)
    ap.add_argument("--max-batch-tokens", type=int, default=4096)
    ap.add_argument("--max-wait", type=float, default=0.02)
    ap.add_argument("--backend", default=None,
                    help="plan backend (default: pallas on a TPU, else vmap)")
    ap.add_argument("--fault-rate", type=float, default=0.0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.traffic:
        return run_traffic(args)
    if args.arch is None:
        ap.error("--arch is required unless --traffic is given")

    cfg = get_config(args.arch).smoke() if args.smoke else get_config(args.arch)
    mesh = make_host_mesh() if args.smoke else make_device_mesh()
    max_len = args.prompt_len + args.gen_len

    decls = M.decl_model(cfg)
    print(f"[serve] {cfg.name}: {param_count(decls)/1e6:.1f}M params")
    params = init_params(decls, jax.random.PRNGKey(args.seed))

    rng = np.random.RandomState(args.seed)
    prompts = rng.randint(1, cfg.vocab, size=(args.batch, args.prompt_len), dtype=np.int32)

    @jax.jit
    def step(params, cache, tok, pos):
        logits, cache = M.decode_step(params, cfg, cache, tok, pos)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

    with jax.set_mesh(mesh):
        vis = None
        if cfg.n_vis_tokens:
            vis = jnp.asarray(rng.randn(args.batch, cfg.n_vis_tokens, cfg.d_model),
                              jnp.dtype(cfg.dtype))
        cache = M.init_cache(params, cfg, args.batch, max_len=max_len, vis_embeds=vis)
        tokens = jnp.asarray(prompts)
        # prompt consumption (token-by-token through the decode path)
        nxt = None
        t0 = time.time()
        for t in range(args.prompt_len):
            if cfg.embed_frontend_stub:
                emb = jax.random.normal(
                    jax.random.PRNGKey(t), (args.batch, 1, cfg.d_model),
                    jnp.dtype(cfg.dtype))
                nxt, cache = step(params, cache, emb, jnp.asarray(t, jnp.int32))
            else:
                nxt, cache = step(params, cache, tokens[:, t:t + 1], jnp.asarray(t, jnp.int32))
        generated = [np.asarray(nxt)]
        for t in range(args.prompt_len, max_len - 1):
            if cfg.embed_frontend_stub:
                emb = params["embed"]  # audio stub has no token embedding table
                raise SystemExit("generation loop for frontend-stub archs needs "
                                 "external frame embeddings; serve supports "
                                 "token archs")
            nxt, cache = step(params, cache, generated[-1][:, None], jnp.asarray(t, jnp.int32))
            generated.append(np.asarray(nxt))
        dt = time.time() - t0
        gen = np.stack(generated, axis=1)
    n_steps = args.prompt_len + len(generated) - 1
    print(f"[serve] {n_steps} decode steps, batch {args.batch}: "
          f"{1000 * dt / n_steps:.1f} ms/step, {args.batch * n_steps / dt:.1f} tok/s")
    print(f"[serve] sample continuation: {gen[0, :16].tolist()}")
    return gen


if __name__ == "__main__":
    main()
