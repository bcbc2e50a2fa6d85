"""The multisplit stage-graph pipeline package (DESIGN.md §10).

The paper's model (§4.1) factors every multisplit variant into
{local prescan} → {one global scan} → {local postscan}; its applications are
partial or iterated instances of that pipeline (histogram = prescan+reduce,
radix sort = the full pipeline per digit pass). This package makes that
structure explicit:

* :mod:`~repro.core.pipeline.stages`   — layout/scan/local-solve primitives.
* :mod:`~repro.core.pipeline.registry` — the declarative backend registry
  ({reference, vmap, pallas-interpret, pallas}); each backend contributes
  capability flags + stage implementations, no if/elif dispatch.
* :mod:`~repro.core.pipeline.tiles`    — the one tile heuristic/autotune
  cache every consumer resolves through.
* :mod:`~repro.core.pipeline.spec`     — :class:`PipelineSpec` (declarative,
  incl. partial ``counts_only``/``positions_only`` modes and flat/batched/
  segmented layouts) and the executable :class:`MultisplitPlan`.
* :mod:`~repro.core.pipeline.radix`    — :class:`RadixPipeline`: chained
  digit passes on resident padded buffers (pad/tile once per sort).
"""

from repro.core.pipeline.autotune import (
    AutotuneConfig,
    autotune_fused2,
    autotune_label_fusion,
    autotune_status,
    set_autotune,
)
from repro.core.pipeline.radix import RadixPipeline, radix_pass_pairs, radix_passes
from repro.core.pipeline.registry import (
    BACKENDS,
    Backend,
    KernelStages,
    StageImpl,
    VmapStages,
    available_backends,
    backend_decisions,
    backend_for,
    backend_names,
    default_backend,
    get_backend,
    register_backend,
)
from repro.core.pipeline.spec import (
    MODES,
    VMAP_FUSION_MAX_BUCKETS,
    MultisplitPlan,
    PipelineSpec,
    Stage,
    fusion_decision,
    fusion_decisions,
    make_batched_plan,
    make_plan,
    make_radix_plan,
    make_segmented_plan,
    make_segmented_radix_plan,
)
from repro.core.pipeline.stages import (
    MultisplitResult,
    direct_counts,
    direct_solve_ids,
    direct_solve_reference,
    exclusive_rows,
    global_scan,
    packed_direct_solve_ids,
    packed_tile_local_offsets,
    pad_rows,
    pad_to_tiles,
    seg_tile_local,
    segment_ids_from_starts,
    tile_local_offsets,
)
from repro.core.pipeline.tiles import (
    BMS_TILE,
    FAMILIES,
    WMS_TILE,
    autotune_tile,
    clear_tile_cache,
    family_decision,
    family_decisions,
    resolve_kernel_family,
    resolve_sub_bits,
    resolve_tile,
)

__all__ = [
    "AutotuneConfig",
    "BACKENDS", "BMS_TILE", "Backend", "FAMILIES", "KernelStages", "MODES",
    "MultisplitPlan", "MultisplitResult", "PipelineSpec", "RadixPipeline",
    "Stage", "StageImpl", "VMAP_FUSION_MAX_BUCKETS", "VmapStages", "WMS_TILE",
    "autotune_fused2", "autotune_label_fusion", "autotune_status",
    "autotune_tile", "available_backends", "backend_decisions",
    "backend_for", "backend_names", "clear_tile_cache", "default_backend",
    "direct_counts",
    "direct_solve_ids", "direct_solve_reference", "exclusive_rows",
    "family_decision", "family_decisions", "fusion_decision",
    "fusion_decisions",
    "get_backend", "global_scan",
    "make_batched_plan", "make_plan", "make_radix_plan",
    "make_segmented_plan", "make_segmented_radix_plan",
    "packed_direct_solve_ids", "packed_tile_local_offsets", "pad_rows",
    "pad_to_tiles", "radix_pass_pairs", "radix_passes", "register_backend",
    "resolve_kernel_family", "resolve_sub_bits", "resolve_tile",
    "seg_tile_local", "segment_ids_from_starts", "set_autotune",
    "tile_local_offsets",
]
