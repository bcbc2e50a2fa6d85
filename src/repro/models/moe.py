"""Mixture-of-Experts with multisplit token dispatch (the paper's technique
as a first-class framework feature — DESIGN.md §4).

Routing a token to an expert IS a multisplit: keys = token indices, bucket
identifier = router argmax, and the dispatch permutation is exactly paper
eq. (2). Three dispatch modes:

* ``dense``      — no permutation at all: every expert runs on every token,
                   combined with router weights. The "compute instead of
                   move" strawman (paper §3.2 scan-based-split analogue).
                   O(n·E) FLOPs; only viable for tiny configs/tests.
* ``sort``       — ranks from a stable argsort of expert ids (the paper's
                   RB-sort baseline: sorting log n-bit payloads when log E
                   bits suffice).
* ``multisplit`` — ranks from the {prescan, scan, postscan} multisplit
                   machinery: tile histograms + ONE exclusive scan +
                   tile-local offsets. No sort network anywhere.

All modes produce identical outputs (up to dropped-token sets, which are
identical between sort and multisplit since both are stable).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_norm, mlp_block, mlp_decl, norm_decl
from repro.parallel.sharding import ParamDecl, constrain as _constrain

Array = jnp.ndarray

DISPATCH_TILE = 2048


class MoEAux(NamedTuple):
    load_balance: Array
    router_z: Array
    drop_fraction: Array


def moe_decl(cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    decl = {
        "norm": norm_decl(cfg),
        "router": ParamDecl((d, e), ("embed", "experts"), scale=0.02),
        "w_gate": ParamDecl((e, d, f), ("experts", "embed", "ff")),
        "w_up": ParamDecl((e, d, f), ("experts", "embed", "ff")),
        "w_down": ParamDecl((e, f, d), ("experts", "ff", "embed")),
    }
    if cfg.moe.shared_expert:
        decl["shared"] = mlp_decl(cfg)
    return decl


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    cap = int(math.ceil(n_tokens * k / e * cfg.moe.capacity_factor))
    return max(8, -(-cap // 8) * 8)


def _router(p, xn: Array, cfg: ModelConfig):
    """xn: (n, d) -> (gates (n, k), experts (n, k), aux parts)."""
    logits = jnp.einsum("nd,de->ne", xn, p["router"].astype(xn.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.moe.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss + z-loss. The top-1 dispatch fraction ce
    # is a counts_only pipeline (the §7.3 histogram applied to routing) —
    # exact integer counts, gradient-free like the one-hot mean it replaces.
    e = cfg.moe.num_experts
    me = probs.mean(0)
    counts, _ = expert_load_stats(experts[:, 0], e)
    ce = counts.astype(jnp.float32) / experts.shape[0]
    lb = e * jnp.sum(me * ce)
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return gates, experts, lb, z


def expert_load_stats(
    expert_ids: Array,
    num_experts: int,
    capacity: Optional[int] = None,
    segment_starts: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Per-expert token load via ``repro.ops`` ``counts_only`` calls
    (DESIGN.md §10/§11): {prescan, tree-reduce}, no scan and no permutation
    — the §7.3 histogram machinery pointed at the router output.  The
    :class:`~repro.ops.IdentitySpec` is hashable, so every MoE layer and
    every step shares ONE trace of the dispatch op.

    Returns ``(counts, overflow_fraction)``: ``counts`` is the (e,) — or
    (s, e) with ``segment_starts`` — expert histogram, and
    ``overflow_fraction`` the fraction of tokens beyond ``capacity`` per
    expert (0.0 when ``capacity`` is None), i.e. the drop rate a
    capacity-bounded dispatch of these assignments would incur.
    """
    from repro import ops

    n = expert_ids.shape[0]
    tile = min(DISPATCH_TILE, max(int(n), 1))
    spec = ops.identity_buckets(num_experts)
    if segment_starts is None:
        counts = ops.multisplit(
            expert_ids, spec, method="dms", tile=tile, mode="counts_only"
        ).bucket_counts
    else:
        counts = ops.segmented_multisplit(
            expert_ids, spec, segment_starts, method="dms", tile=tile,
            mode="counts_only",
        ).bucket_counts
    if capacity is None or n == 0:
        return counts, jnp.zeros((), jnp.float32)
    dropped = jnp.maximum(counts - capacity, 0).sum()
    return counts, dropped.astype(jnp.float32) / n


def _ranks_multisplit(
    expert_ids: Array, num_experts: int, segment_starts: Optional[Array] = None
) -> Tuple[Array, Array]:
    """Stable rank of each virtual token within its expert + expert counts.

    THE paper technique, executed as ONE ``positions_only``
    ``repro.ops.multisplit`` call (DESIGN.md §10: prescan, one global scan,
    postscan positions — the reordered-keys stage never runs, and nothing
    but the eq. (2) permutation is materialized). With ``segment_starts``
    the call is a single SEGMENTED multisplit (DESIGN.md §9): ranks restart
    per segment and ``counts`` is the (s, e) per-segment expert histogram —
    per-request routing in one launch instead of a host loop over requests.
    """
    from repro import ops

    n = expert_ids.shape[0]
    tile = min(DISPATCH_TILE, max(int(n), 1))
    if segment_starts is None:
        res = ops.multisplit(
            expert_ids, ops.identity_buckets(num_experts), method="dms",
            tile=tile, mode="positions_only",
        )
        ranks = res.permutation - res.bucket_starts[expert_ids]
        return ranks.astype(jnp.int32), res.bucket_counts
    ranks, counts, _ = _segmented_ranks(
        expert_ids, jnp.asarray(segment_starts, jnp.int32), num_experts, tile
    )
    return ranks, counts


def _segmented_ranks(
    expert_ids: Array, seg: Array, num_experts: int, tile: int,
    backend: Optional[str] = None,
) -> Tuple[Array, Array, Array]:
    """One segmented ``positions_only`` ``repro.ops`` call -> (ranks, (s, e)
    counts, seg_ids); the derived per-token segment id is returned so
    hot-path callers don't recompute the searchsorted."""
    from repro import ops
    from repro.core.pipeline import segment_ids_from_starts

    n = expert_ids.shape[0]
    res = ops.segmented_multisplit(
        expert_ids, ops.identity_buckets(num_experts), seg, method="dms",
        tile=tile, mode="positions_only", backend=backend,
    )
    seg_ids = segment_ids_from_starts(seg, n)
    ranks = res.permutation - res.bucket_starts[seg_ids, expert_ids]
    return ranks.astype(jnp.int32), res.bucket_counts, seg_ids


def route_tokens_segmented(
    expert_ids: Array,
    segment_starts: Array,
    num_experts: int,
    capacity: int,
    *,
    backend: Optional[str] = None,
) -> Tuple[Array, Array, Array]:
    """Per-request token routing: ONE segmented multisplit call assigns every
    virtual token a slot in its request's (expert, capacity) block.

    ``expert_ids`` is the flat concatenation of per-request expert
    assignments; ``segment_starts`` the (s,) request boundaries. Returns
    ``(slot, keep, counts)``: ``slot[i] = (seg_i·E + expert_i)·capacity +
    rank_i`` for kept tokens (an index into a (s·E·capacity,) dispatch
    buffer; dropped tokens point one past the end), the per-token keep mask
    (rank < capacity, stable within each (request, expert) pair), and the
    (s, E) per-request expert load. This is the building block for
    capacity-per-request batched serving — :class:`repro.serving.ServerLoop`
    calls it once per step (ROADMAP "heavy traffic"). ``s == 0`` (a
    zero-request step) returns empty slots and (0, E) counts; zero-length
    segments (a user with no tokens this step) get all-zero count rows.
    ``backend`` selects the plan backend of the one segmented launch
    (``None``: the ``repro.ops`` default).
    """
    n = expert_ids.shape[0]
    seg = jnp.asarray(segment_starts, jnp.int32)
    s = int(seg.shape[0])
    tile = min(DISPATCH_TILE, max(int(n), 1))
    ranks, counts, seg_ids = _segmented_ranks(
        expert_ids, seg, num_experts, tile, backend=backend
    )
    keep = ranks < capacity
    slot = jnp.where(
        keep,
        (seg_ids * num_experts + expert_ids) * capacity + ranks,
        s * num_experts * capacity,
    )
    return slot.astype(jnp.int32), keep, counts


def _ranks_sort(expert_ids: Array, num_experts: int) -> Tuple[Array, Array]:
    """Baseline: ranks via stable argsort (RB-sort analogue)."""
    n = expert_ids.shape[0]
    order = jnp.argsort(expert_ids, stable=True)
    one_hot = jax.nn.one_hot(expert_ids, num_experts, dtype=jnp.int32)
    counts = one_hot.sum(0)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(n, dtype=jnp.int32)
    ranks_sorted = pos_sorted - starts[expert_ids[order]]
    ranks = jnp.zeros((n,), jnp.int32).at[order].set(ranks_sorted)
    return ranks, counts.astype(jnp.int32)


def _expert_ffn(p, x: Array, dtype) -> Array:
    """x: (E, C, d) -> (E, C, d), SwiGLU per expert (batched over E)."""
    gate = jnp.einsum("ecd,edf->ecf", x, p["w_gate"].astype(dtype))
    up = jnp.einsum("ecd,edf->ecf", x, p["w_up"].astype(dtype))
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(dtype) * up
    return jnp.einsum("ecf,efd->ecd", act, p["w_down"].astype(dtype))


def _dispatch_multisplit_ep(p, xn, gates, experts, cfg: ModelConfig, cap: int, dtype):
    """Manual expert-parallel dispatch under shard_map (dispatch="multisplit_ep").

    The hillclimbed path (EXPERIMENTS.md §Perf): GSPMD's automatic plan for
    the dispatch gathers materializes full-size fp32 partial outputs on every
    model rank and all-reduces them. Here the paper's {local, global, local}
    model is mapped by hand:

      * local:  each (data, model) device multisplits ITS token shard by
                expert id restricted to ITS model-rank's expert group
                (prescan/scan/postscan on a (n_loc,) shard — pure local math);
      * global: the ONLY collective is one bf16 psum of the combined output
                over the model axis (tokens are replicated across "model",
                experts are sharded across it — no token movement at all);
      * local:  capacity-bounded gather + grouped FFN + weighted combine.

    Capacity is per-data-shard (cap / DP), the standard local-capacity MoE
    semantics. Output matches the GSPMD path exactly when nothing drops.
    """
    mesh = jax.sharding.get_abstract_mesh()
    names = getattr(mesh, "axis_names", ()) or ()
    if "model" not in names:
        return None  # no mesh context (smoke tests): caller falls back
    from jax.sharding import PartitionSpec as P

    dp_axes = tuple(a for a in names if a in ("pod", "data"))
    dp_entry = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    n, d = xn.shape
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    tp = mesh.shape["model"]
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    if e % tp != 0 or n % n_dp != 0:
        return None
    e_loc = e // tp
    cap_loc = max(8, ((-(-cap // n_dp) + 7) // 8) * 8)

    wg_spec = P("model", None, None)
    fsdp = False  # expert weights dp-gathered inside if their decl is fsdp-sharded

    def body(xn_l, gates_l, experts_l, wg_l, wu_l, wd_l):
        j = jax.lax.axis_index("model")
        n_loc = xn_l.shape[0]
        lo = j * e_loc
        flat_e = experts_l.reshape(-1)                        # (n_loc·k,)
        in_group = (flat_e >= lo) & (flat_e < lo + e_loc)
        sub_ids = jnp.where(in_group, flat_e - lo, e_loc)     # bucket e_loc = foreign
        ranks, _ = _ranks_multisplit(sub_ids, e_loc + 1)      # paper machinery
        keep = in_group & (ranks < cap_loc)
        slot = jnp.where(keep, sub_ids * cap_loc + ranks, e_loc * cap_loc)
        token_idx = jnp.arange(n_loc * k, dtype=jnp.int32) // k
        token_for_slot = jnp.full((e_loc * cap_loc,), n_loc, jnp.int32).at[slot].set(
            token_idx, mode="drop"
        )
        valid = (token_for_slot < n_loc)[:, None].astype(dtype)
        expert_in = jnp.take(
            xn_l, jnp.minimum(token_for_slot, n_loc - 1), axis=0, mode="clip"
        ) * valid
        expert_out = _expert_ffn(
            {"w_gate": wg_l, "w_up": wu_l, "w_down": wd_l},
            expert_in.reshape(e_loc, cap_loc, d), dtype,
        ).reshape(e_loc * cap_loc, d)
        w = (gates_l * keep.reshape(n_loc, k)).astype(dtype)
        slot_nk = jnp.minimum(slot.reshape(n_loc, k), e_loc * cap_loc - 1)
        y = jnp.zeros((n_loc, d), dtype)
        for kk in range(k):
            y = y + jnp.take(expert_out, slot_nk[:, kk], axis=0, mode="clip") \
                * w[:, kk:kk + 1]
        # the ONE global op: combine partial outputs across expert groups
        y = jax.lax.psum(y, "model")
        # each virtual token is kept on exactly one model rank =>
        # global kept fraction = tp * mean(keep); drop = 1 - that
        drop_l = 1.0 - tp * keep.mean()
        return y, jax.lax.pmean(drop_l, ("model",) + dp_axes)[None]

    y, drop = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dp_entry, None), P(dp_entry, None), P(dp_entry, None),
                  wg_spec, wg_spec, wg_spec),
        out_specs=(P(dp_entry, None), P(None)),
        check_vma=False,
    )(xn, gates, experts,
      p["w_gate"].astype(dtype), p["w_up"].astype(dtype), p["w_down"].astype(dtype))
    return y, drop[0]


def moe_block(p, x: Array, cfg: ModelConfig) -> Tuple[Array, MoEAux]:
    """x: (B, S, d) -> (residual delta, aux losses)."""
    b, s, d = x.shape
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    dtype = x.dtype
    # the (b, s) -> (n,) flatten merges the dp-sharded batch dim; without an
    # explicit anchor GSPMD replicates the flat activations (observed 24 GiB
    # fp32 copies + full-size scatter-add gradients on dbrx)
    xn = _constrain(apply_norm(p["norm"], x, cfg).reshape(b * s, d), "dp", None)
    n = b * s
    gates, experts, lb, z = _router(p, xn, cfg)

    if cfg.moe.dispatch == "dense":
        # run every expert on every token (no data movement, O(n·E) compute)
        all_out = _expert_ffn(p, jnp.broadcast_to(xn[None], (e, n, d)), dtype)  # (E, n, d)
        combine = jnp.zeros((n, e), jnp.float32)
        combine = jax.vmap(lambda c, ex, g: c.at[ex].add(g))(combine, experts, gates)
        y = jnp.einsum("ne,end->nd", combine.astype(dtype), all_out)
        drop = jnp.zeros((), jnp.float32)
    elif cfg.moe.dispatch == "multisplit_ep":
        out = _dispatch_multisplit_ep(p, xn, gates, experts, cfg, _capacity(n, cfg), dtype)
        if out is None:   # no mesh in scope: fall back to the GSPMD path
            import dataclasses as _dc

            return moe_block(
                p, x, _dc.replace(cfg, moe=_dc.replace(cfg.moe, dispatch="multisplit"))
            )
        y, drop = out
        y = y.reshape(b, s, d)
        if cfg.moe.shared_expert:
            y = y + mlp_block(p["shared"], x, cfg)
        return y, MoEAux(lb, z, drop)
    else:
        cap = _capacity(n, cfg)
        flat_experts = experts.reshape(-1)                          # (n·k,) virtual tokens
        if cfg.moe.dispatch == "multisplit":
            ranks, counts = _ranks_multisplit(flat_experts, e)
        elif cfg.moe.dispatch == "sort":
            ranks, counts = _ranks_sort(flat_experts, e)
        else:
            raise ValueError(f"unknown dispatch {cfg.moe.dispatch!r}")

        keep = ranks < cap
        slot = jnp.where(keep, flat_experts * cap + ranks, e * cap)  # OOB -> dropped
        token_idx = jnp.arange(n * k, dtype=jnp.int32) // k
        token_for_slot = jnp.full((e * cap,), n, jnp.int32).at[slot].set(
            token_idx, mode="drop"
        )
        # Sharding hygiene: NO +1-row pad concatenates — a (n+1, d) tensor
        # can't keep the batch sharding (n+1 doesn't divide) and GSPMD then
        # replicates the gather operand AND all-reduces its fp32 gradient at
        # full (n·k, d) size (observed: 96 GiB/op on dbrx). Clamp + mask
        # keeps every tensor shardable; masks zero out invalid lanes.
        valid_slot = (token_for_slot < n)[:, None].astype(dtype)     # (E·C, 1)
        expert_in = jnp.take(
            xn, jnp.minimum(token_for_slot, n - 1), axis=0,
            mode="clip",  # pre-clamped: no OOB fill/select machinery
        ) * valid_slot
        expert_in = expert_in.reshape(e, cap, d)
        # EP over model axis x DP over the capacity dim: expert compute is
        # 2-D sharded like everything else (tokens reach their expert shard
        # via the all-to-all GSPMD inserts for the gather).
        expert_in = _constrain(expert_in, "model", "dp", None)
        expert_out = _expert_ffn(p, expert_in, dtype)                # (E, C, d)
        expert_out = _constrain(expert_out, "model", "dp", None)
        flat_out = expert_out.reshape(e * cap, d)
        # Combine as a static loop over the k routed experts: one (n, d)
        # bf16 gather each, dp-anchored. (An einsum over a materialized
        # (n, k, d) tensor gets upcast to fp32 accumulation by XLA and
        # the reshape-merged sharding is lost — observed 96 GiB fp32
        # replicated tensors; the k-loop form stays bf16 and sharded.
        # Dropped slots: gate x keep == 0 kills the clamped garbage row.)
        w = (gates * keep.reshape(n, k)).astype(dtype)               # (n, k)
        slot_nk = jnp.minimum(slot.reshape(n, k), e * cap - 1)
        y = jnp.zeros((n, d), dtype)
        for kk in range(k):
            pick = jnp.take(
                flat_out, _constrain(slot_nk[:, kk], "dp"), axis=0,
                mode="clip",
            )
            y = y + _constrain(pick, "dp", None) * w[:, kk:kk + 1]
        y = _constrain(y, "dp", None)
        drop = 1.0 - keep.mean()

    y = y.reshape(b, s, d)
    if cfg.moe.shared_expert:
        y = y + mlp_block(p["shared"], x, cfg)   # always-on shared expert (own pre-norm)

    return y, MoEAux(lb, z, drop)
