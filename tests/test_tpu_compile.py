"""Compile the main-path kernels for a described v5e chip (no chip needed).

Each case compiles one kernel entry point at the tile the heuristic picks
for n = 2^25 — the paper's headline size — with Mosaic's scoped-VMEM limit
set to the heuristic's own cost model for that tile. A pass shows that the
kernel lowers under Mosaic (the program holds a ``tpu_custom_call``) and
that the cost model bounds what the compiler allocates, so every tile the
heuristic admits compiles. The topology is described inside a fixture, so
only the test worker that runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.identifiers import BitfieldSpec, DeltaSpec, IdentitySpec
from repro.core.pipeline import tiles
from repro.kernels import multisplit_tile as mst
from repro.models.moe import DISPATCH_TILE

N = 1 << 25
L = 8                                   # tiles per compiled program


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _flat(m, key_value):
    """Fused postscan+reorder of a flat multisplit, heuristic family/tile."""
    family = tiles._heuristic_family(N, m, "bms", "pallas")[0]
    t = tiles._heuristic_tile(N, m, "bms", "pallas", family=family)
    spec = DeltaSpec(m, 1 << 32)
    budget = tiles._family_cost_bytes(t, m, family, oblivious=True)
    if family == "packed":
        fn = lambda k, g, v: mst.packed_fused_postscan_reorder_pallas(
            k, g, None, v, spec=spec, interpret=False)
    else:
        fn = lambda k, g, v: mst.spec_fused_postscan_reorder_pallas(
            k, g, v, spec, interpret=False)
    return fn, t, m, budget, key_value


def _radix_pass(key_value):
    """One chained radix pass (8-bit digit) at the radix plan's tile."""
    m = 256
    family = tiles._heuristic_family(N, m, "bms", "pallas")[0]
    t = tiles._heuristic_tile(N, m, "bms", "pallas", family=family)
    spec = BitfieldSpec(8, 8)
    fn = lambda k, g, v: mst.packed_fused_postscan_reorder_pallas(
        k, g, None, v, spec=spec, interpret=False)
    return fn, t, m, tiles._family_cost_bytes(t, m, family, oblivious=True), key_value


def _routing():
    """The serving step's segmented positions kernel: 8 experts x 65 request
    segments (ServingConfig defaults) at the largest token class, on the
    routing's explicit dispatch tile, within the kernels' VMEM limit."""
    e, s, n_tok = 8, 65, 4096
    t = min(DISPATCH_TILE, n_tok)
    spec = IdentitySpec(e)
    fn = lambda k, seg, g: mst.packed_tile_positions_pallas(
        k, g, e, spec=spec, seg_tiled=seg, num_segments=s, interpret=False)
    return fn, t, e * s, mst.VMEM_LIMIT_BYTES


CASES = {
    "flat-m2-keys": lambda: _flat(2, False),
    "flat-m2-kv": lambda: _flat(2, True),
    "flat-m32-keys": lambda: _flat(32, False),
    "flat-m32-kv": lambda: _flat(32, True),
    "flat-m256-keys": lambda: _flat(256, False),
    "flat-m256-kv": lambda: _flat(256, True),
    "radix-pass-keys": lambda: _radix_pass(False),
    "radix-pass-kv": lambda: _radix_pass(True),
    "segmented-routing": None,
}


@pytest.mark.parametrize("case", list(CASES))
def test_main_path_kernel_compiles_for_v5e(case, one_chip, monkeypatch):
    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if case == "segmented-routing":
        fn, t, m_eff, budget = _routing()
        args = (sds((L, t)), sds((L, t)), sds((L, m_eff)))
    else:
        fn, t, m_eff, budget, key_value = CASES[case]()
        if not key_value:
            kernel = fn
            fn = lambda k, g: kernel(k, g, None)
        args = (sds((L, t), jnp.uint32), sds((L, m_eff)))
        args += (sds((L, t)),) if key_value else ()
    assert budget <= mst.VMEM_LIMIT_BYTES
    # the cost model must bound what Mosaic allocates for this tile
    monkeypatch.setattr(mst, "VMEM_LIMIT_BYTES", budget)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def tpu_attached(monkeypatch):
    """Code that asks whether a TPU is attached is told yes, so that the
    default backend is the one it would be on the described chips."""
    from repro.kernels import ops as kops

    monkeypatch.setattr(kops, "_tpu_available", lambda: True)
    monkeypatch.delenv("REPRO_INTERPRET", raising=False)


def test_sharded_default_path_compiles_for_v5e_2x2(topo, tpu_attached):
    """The four-chip key-value multisplit with default arguments: the local
    stage's kernels inside ``shard_map``, then the exchange's collectives.
    The exchange ships each peer's run as one slice: the program holds
    all-to-alls, none of a 32-bit signed operand (positions), and no gather."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.distributed import make_multisplit_sharded
    from repro.core.pipeline import backend_decisions

    mesh = Mesh(topo.devices, ("x",))
    n_shard = 1 << 14
    arg = jax.ShapeDtypeStruct((4 * n_shard,), jnp.uint32,
                               sharding=NamedSharding(mesh, P("x")))
    fn = make_multisplit_sharded(DeltaSpec(256, 1 << 32), mesh, "x", key_value=True)
    text = jax.jit(fn).lower(arg, arg).compile().as_text()
    assert "tpu_custom_call" in text and "all-to-all" in text
    assert backend_decisions()[(n_shard, "uint32")] == ("pallas", "tpu+32-bit keys")
    ops = re.findall(r"= (\S+) ([\w-]+)\(", text)                # (result type, opcode)
    assert not [op for _, op in ops if op == "gather"]
    assert not [ty for ty, op in ops if op == "all-to-all" and ty.startswith("s32")]


def test_routing_under_an_auto_sharded_mesh_compiles_for_v5e_2x2(topo, tpu_attached):
    """The MoE router's load count runs outside ``shard_map`` under the
    expert-parallel mesh, where a Mosaic kernel cannot be partitioned: the
    default must keep it off the kernels."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.models.moe import expert_load_stats

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    ids = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        compiled = jax.jit(lambda e: expert_load_stats(e, 16)[0]).lower(ids).compile()
    assert "tpu_custom_call" not in compiled.as_text()
