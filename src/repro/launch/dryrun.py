import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init). Everything below may import jax.

"""Multi-pod dry-run: lower + compile every (architecture x input shape) cell
on the production meshes and record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k --mesh single                       # one cell
    PYTHONPATH=src python -m repro.launch.dryrun --all       # everything

Artifacts: artifacts/dryrun/{arch}__{shape}__{mesh}.json — consumed by
benchmarks/roofline.py and EXPERIMENTS.md.
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, ALIASES, get_config
from repro.configs.base import ParallelConfig, TrainConfig
from repro.launch import steps as S
from repro.launch.hlo_analysis import dominant_term, parse_collectives, roofline_terms
from repro.launch.input_specs import SHAPES, batch_specs, decode_specs, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.parallel.sharding import param_count

ART_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

FSDP_THRESHOLD = 50e9      # params; larger archs shard params/opt over data
BF16_MOMENTS_THRESHOLD = 300e9


def parallel_config(cfg, mesh, n_params: int) -> ParallelConfig:
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return ParallelConfig(fsdp=n_params > FSDP_THRESHOLD, dp_axes=dp)


def train_config(n_params: int) -> TrainConfig:
    return TrainConfig(
        moments_dtype="bfloat16" if n_params > BF16_MOMENTS_THRESHOLD else "float32"
    )


def model_flops_estimate(cfg, decls, shape: str) -> float:
    """MODEL_FLOPS: 6·N_active·D for training, 2·N_active·D for inference."""
    n_total = param_count(decls)
    n_active = active_param_count(cfg, decls)
    info = SHAPES[shape]
    if info["kind"] == "train":
        tokens = info["batch"] * info["seq"]
        return 6.0 * n_active * tokens
    if info["kind"] == "prefill":
        tokens = info["batch"] * info["seq"]
        return 2.0 * n_active * tokens
    return 2.0 * n_active * info["batch"]          # decode: one token per row


def active_param_count(cfg, decls) -> float:
    n_total = param_count(decls)
    if not cfg.moe.num_experts:
        return float(n_total)
    # subtract non-routed fraction of expert params
    import numpy as np

    expert_params = 0
    for blk in decls["blocks"]:
        if "moe" in blk:
            for key in ("w_gate", "w_up", "w_down"):
                expert_params += int(np.prod(blk["moe"][key].shape))
    frac = cfg.moe.top_k / cfg.moe.num_experts
    return float(n_total - expert_params * (1.0 - frac))


def _lower_and_compile(cfg, shape, mesh, pcfg, tc, capture_hlo_to=None):
    """Lower + compile one graph; return (cost, mem, collectives, timings)."""
    decls = M.decl_model(cfg)
    kind = SHAPES[shape]["kind"]
    t0 = time.time()
    with jax.set_mesh(mesh):
        if kind == "train":
            step = S.make_train_step(cfg, tc)
            st_sh = S.state_shardings(decls, pcfg, mesh, tc)
            st_abs = S.abstract_state(decls, tc)
            batch_abs = batch_specs(cfg, shape, with_labels=True)
            b_sh = S.batch_sharding(cfg, mesh, batch_abs)
            jitted = jax.jit(
                step, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(st_abs, batch_abs)
        elif kind == "prefill":
            step = S.make_prefill_step(cfg)
            p_sh = S.state_shardings(decls, pcfg, mesh, tc).params
            p_abs = S.abstract_state(decls, tc).params
            batch_abs = batch_specs(cfg, shape, with_labels=False)
            b_sh = S.batch_sharding(cfg, mesh, batch_abs)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(p_abs, batch_abs)
        else:  # decode
            step = S.make_decode_step(cfg)
            p_sh = S.state_shardings(decls, pcfg, mesh, tc).params
            p_abs = S.abstract_state(decls, tc).params
            cache_abs, token_abs, pos_abs = decode_specs(cfg, shape)
            c_sh = S.cache_shardings(cfg, mesh, SHAPES[shape]["batch"])
            t_sh = S.batch_sharding(cfg, mesh, token_abs)
            jitted = jax.jit(
                step, in_shardings=(p_sh, c_sh, t_sh, None),
                out_shardings=(None, c_sh), donate_argnums=(1,),
            )
            lowered = jitted.lower(p_abs, cache_abs, token_abs, pos_abs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    cost = dict(compiled.cost_analysis() or {})
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            k: int(getattr(mem, k))
            for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes")
            if hasattr(mem, k)
        }
    except Exception as e:  # pragma: no cover
        mem_rec = {"error": str(e)}
    hlo = compiled.as_text()
    if capture_hlo_to:
        Path(capture_hlo_to).write_text(hlo)
    colls = parse_collectives(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": sum(v["operand_bytes"] for v in colls.values()),
        "collectives": colls,
        "memory": mem_rec,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }


# Inner-scan chunk overrides so the unit lowerings can unroll everything:
# cost_analysis counts while bodies once, so every loop in the unit graphs
# must be unrolled for exact accounting (DESIGN.md §8, EXPERIMENTS.md §Dry-run).
_UNIT_OVERRIDES = {
    "train_4k": {"attn_chunk": 1024, "ssd_chunk": 1024, "loss_chunk": 1024},
    "prefill_32k": {"attn_chunk": 4096, "ssd_chunk": 4096, "loss_chunk": 8192},
    "decode_32k": {},
    "long_500k": {},
}


def lower_cell(arch: str, shape: str, mesh_kind: str, capture_hlo_to=None,
               cfg_overrides=None, tc_overrides=None):
    """Lower + compile one cell.

    Two accountings:
      * FULL graph (scanned layers): the deployable artifact — proves the
        sharding compiles, gives memory_analysis and the collective schedule.
      * COST via two-point delta: XLA's cost_analysis counts while-loop
        bodies once, so we compile unit graphs at 1x and 2x the layer
        pattern with ALL inner scans unrolled; per-superblock cost =
        unit2 - unit1, total = unit1 + (n_layers/pattern - 1) * delta.
        (sLSTM's time recurrence stays a loop — its FLOPs are analytically
        folded into MODEL_FLOPS instead; see EXPERIMENTS.md.)
    """
    import dataclasses as _dc

    cfg = get_config(arch)
    if cfg_overrides:
        moe_over = {k[4:]: v for k, v in cfg_overrides.items() if k.startswith("moe_")}
        plain = {k: v for k, v in cfg_overrides.items() if not k.startswith("moe_")}
        if plain:
            cfg = _dc.replace(cfg, **plain)
        if moe_over:
            cfg = _dc.replace(cfg, moe=_dc.replace(cfg.moe, **moe_over))
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape, "mesh": mesh_kind, "status": "skipped",
                "reason": "full-attention arch; long_500k requires sub-quadratic attention"}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    decls = M.decl_model(cfg)
    n_params = param_count(decls)
    pcfg = parallel_config(cfg, mesh, n_params)
    tc = train_config(n_params)
    if tc_overrides:
        tc = _dc.replace(tc, **tc_overrides)

    full = _lower_and_compile(cfg, shape, mesh, pcfg, tc, capture_hlo_to=capture_hlo_to)

    if mesh_kind == "multi":
        # The multi-pod pass proves the "pod" axis shards (full compile
        # above); the roofline table is single-pod only per the brief —
        # skip the unit-accounting compiles.
        return {
            "arch": arch, "shape": shape, "mesh": mesh_kind, "status": "ok",
            "n_chips": int(mesh.devices.size), "n_params": int(n_params),
            "fsdp": pcfg.fsdp, "moments_dtype": tc.moments_dtype,
            "lower_s": full["lower_s"], "compile_s": full["compile_s"],
            "collectives_full_graph": full["collectives"],
            "memory_analysis": full["memory"],
            "roofline": None, "dominant": None,
        }

    import dataclasses

    pattern, n_super, tail = M.block_pattern(cfg)
    plen = len(pattern)
    over = dict(_UNIT_OVERRIDES[shape], unroll_scans=True)
    cfg1 = dataclasses.replace(cfg, n_layers=plen, **over)
    cfg2 = dataclasses.replace(cfg, n_layers=2 * plen, **over)
    unit1 = _lower_and_compile(cfg1, shape, mesh, pcfg, tc)
    unit2 = _lower_and_compile(cfg2, shape, mesh, pcfg, tc)

    n_chips = mesh.devices.size
    mult = cfg.n_layers / plen          # fractional superblocks cover the tail
    corrected = {}
    for key in ("flops", "bytes", "collective_bytes"):
        delta = unit2[key] - unit1[key]
        # cost_analysis / HLO shapes are PER-DEVICE post-partitioning;
        # scale to global so HLO_FLOPs / (chips * peak) is the per-chip time.
        corrected[key] = (unit1[key] + (mult - 1.0) * delta) * n_chips
    terms = roofline_terms(
        corrected["flops"], corrected["bytes"], corrected["collective_bytes"], n_chips
    )
    mflops = model_flops_estimate(cfg, decls, shape)

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "status": "ok",
        "n_chips": int(n_chips),
        "n_params": int(n_params),
        "n_params_active": int(active_param_count(cfg, decls)),
        "fsdp": pcfg.fsdp,
        "moments_dtype": tc.moments_dtype,
        "lower_s": full["lower_s"], "compile_s": full["compile_s"],
        "unit_compile_s": [unit1["compile_s"], unit2["compile_s"]],
        "unit_raw": {
            "unit1": {k: unit1[k] for k in ("flops", "bytes", "collective_bytes")},
            "unit2": {k: unit2[k] for k in ("flops", "bytes", "collective_bytes")},
        },
        "hlo_flops": corrected["flops"], "hlo_bytes": corrected["bytes"],
        "collective_bytes": corrected["collective_bytes"],
        "hlo_flops_scanned_raw": full["flops"],
        "collectives_full_graph": full["collectives"],
        "collectives_per_superblock": {
            k: {
                "count": unit2["collectives"].get(k, {}).get("count", 0)
                - unit1["collectives"].get(k, {}).get("count", 0),
                "operand_bytes": unit2["collectives"].get(k, {}).get("operand_bytes", 0)
                - unit1["collectives"].get(k, {}).get("operand_bytes", 0),
            }
            for k in set(unit1["collectives"]) | set(unit2["collectives"])
        },
        "memory_analysis": full["memory"],
        "roofline": terms,
        "dominant": dominant_term(terms),
        "model_flops": mflops,
        "useful_fraction": (mflops / corrected["flops"]) if corrected["flops"] else None,
    }
    return rec


def run_cells(cells, out_dir: Path, hlo_dir=None, variant: str = "",
              cfg_overrides=None, tc_overrides=None):
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = f"__{variant}" if variant else ""
    for arch, shape, mesh_kind in cells:
        name = f"{arch}__{shape}__{mesh_kind}{suffix}"
        out_path = out_dir / f"{name}.json"
        if out_path.exists():
            print(f"[skip cached] {name}")
            continue
        print(f"[dryrun] {name} ...", flush=True)
        try:
            hlo_to = (Path(hlo_dir) / f"{name}.hlo.txt") if hlo_dir else None
            rec = lower_cell(arch, shape, mesh_kind, capture_hlo_to=hlo_to,
                             cfg_overrides=cfg_overrides, tc_overrides=tc_overrides)
            if variant:
                rec["variant"] = variant
        except Exception as e:
            rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        out_path.write_text(json.dumps(rec, indent=2, default=str))
        status = rec["status"]
        extra = ""
        if status == "ok":
            extra = f" compile={rec.get('compile_s')}s"
            if rec.get("hlo_flops") is not None:
                extra += f" dominant={rec['dominant']} flops={rec['hlo_flops']:.3g}"
        print(f"[done] {name}: {status}{extra}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", help="architecture id (repeatable)")
    ap.add_argument("--shape", action="append", choices=list(SHAPES))
    ap.add_argument("--mesh", action="append", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(ART_DIR))
    ap.add_argument("--hlo-dir", default=None, help="also dump optimized HLO text")
    ap.add_argument("--variant", default="", help="artifact suffix for perf variants")
    ap.add_argument("--cfg-set", action="append", default=[],
                    help="ModelConfig override k=v (moe_* targets the MoE sub-config)")
    ap.add_argument("--tc-set", action="append", default=[],
                    help="TrainConfig override k=v")
    args = ap.parse_args()

    def parse_kv(items):
        out = {}
        for it in items:
            k, v = it.split("=", 1)
            if v in ("true", "True"):
                v = True
            elif v in ("false", "False"):
                v = False
            else:
                try:
                    v = int(v)
                except ValueError:
                    try:
                        v = float(v)
                    except ValueError:
                        pass
            out[k] = v
        return out

    archs = args.arch or (list(ALIASES) if args.all or not args.arch else [])
    shapes = args.shape or list(SHAPES)
    meshes = args.mesh or ["single", "multi"]
    cells = [(a, s, m) for a in archs for s in shapes for m in meshes]
    run_cells(cells, Path(args.out), hlo_dir=args.hlo_dir, variant=args.variant,
              cfg_overrides=parse_kv(args.cfg_set) or None,
              tc_overrides=parse_kv(args.tc_set) or None)


if __name__ == "__main__":
    main()
