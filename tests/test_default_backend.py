"""The backend a call runs on when it names none: the compiled ``pallas``
kernels on a TPU for 32-bit keys, ``vmap`` everywhere else. A TPU is
simulated by patching ``kernels.ops._tpu_available``; such calls are only
traced to a jaxpr here, since the kernels cannot lower for the CPU."""

import glob
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import ops
from repro.core import histogram as core_histogram
from repro.core import multisplit as core_multisplit
from repro.core.pipeline import backend_decisions, backend_for, default_backend
from repro.kernels import ops as kops
from repro.serving import ServingConfig

N = 1 << 12


@pytest.fixture
def tpu(monkeypatch):
    """A TPU attached, and REPRO_INTERPRET unset."""
    monkeypatch.setattr(kops, "_tpu_available", lambda: True)
    monkeypatch.delenv("REPRO_INTERPRET", raising=False)


@pytest.fixture
def no_tpu(monkeypatch):
    monkeypatch.setattr(kops, "_tpu_available", lambda: False)
    monkeypatch.delenv("REPRO_INTERPRET", raising=False)


def _kernels_in(fn, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("dtype, backend, reason", [
    (jnp.uint32, "pallas", "tpu+32-bit keys"),
    (jnp.int32, "pallas", "tpu+32-bit keys"),
    (jnp.float32, "pallas", "tpu+32-bit keys"),
    (jnp.uint16, "vmap", "16-bit keys"),
    (jnp.uint8, "vmap", "8-bit keys"),
    (np.uint64, "vmap", "64-bit keys"),
])
def test_on_a_tpu_only_32_bit_keys_take_the_kernels(tpu, dtype, backend, reason):
    assert default_backend(N + 1, dtype) == backend
    assert backend_decisions()[(N + 1, jnp.dtype(dtype).name)] == (backend, reason)


def test_without_a_tpu_the_default_is_vmap(no_tpu):
    assert default_backend(N + 3, jnp.uint32) == "vmap"
    assert backend_decisions()[(N + 3, "uint32")] == ("vmap", "no TPU")


@pytest.mark.parametrize("value", ["1", "true"])
def test_repro_interpret_keeps_the_default_off_the_kernels(tpu, monkeypatch, value):
    monkeypatch.setenv("REPRO_INTERPRET", value)
    assert default_backend(N + 4, jnp.uint32) == "vmap"
    assert backend_decisions()[(N + 4, "uint32")] == ("vmap", "REPRO_INTERPRET")


def test_this_host_resolves_every_default_to_vmap():
    """The CPU the tests run on: every entry point's default is vmap."""
    keys = jnp.arange(N, dtype=jnp.uint32)
    assert default_backend(N, keys.dtype) == "vmap"
    assert ServingConfig().backend == "vmap"
    assert not _kernels_in(lambda k: ops.multisplit(k, ops.delta_buckets(4, N)), keys)
    assert not _kernels_in(lambda k: ops.radix_sort(k)[0], keys)


def test_facade_defaults_reach_the_kernels_on_a_tpu(tpu):
    keys = jnp.arange(N, dtype=jnp.uint32)
    seg = jnp.array([0, N // 2], jnp.int32)
    spec = ops.delta_buckets(16, N)
    for fn in (lambda k: ops.multisplit(k, spec),
               lambda k: ops.multisplit_key_value(k, k, spec),
               lambda k: ops.segmented_multisplit(k, spec, seg),
               lambda k: ops.histogram(k, spec),
               lambda k: ops.radix_sort(k)[0],
               lambda k: ops.segmented_radix_sort(k, seg)[0]):
        assert _kernels_in(fn, keys)
    assert backend_decisions()[(N, "uint32")] == ("pallas", "tpu+32-bit keys")


def test_backend_for_resolves_a_name_or_asks_the_default(no_tpu):
    """A named backend is looked up in the registry and asks no default;
    ``None`` is the default's answer; an unknown name is refused, also by
    the serving config."""
    assert backend_for("pallas-interpret", N + 6, jnp.uint32) == "pallas-interpret"
    assert (N + 6, "uint32") not in backend_decisions()
    assert backend_for(None, N + 6, jnp.uint32) == "vmap"
    assert backend_decisions()[(N + 6, "uint32")] == ("vmap", "no TPU")
    with pytest.raises(ValueError, match="unknown backend"):
        backend_for("cuda", N + 6, jnp.uint32)
    with pytest.raises(ValueError, match="unknown backend"):
        ServingConfig(backend="cuda")


CORE_ENTRIES = {
    "multisplit": lambda k, spec, seg: core_multisplit.multisplit(k, spec).keys,
    "batched_multisplit": lambda k, spec, seg: core_multisplit.batched_multisplit(
        k.reshape(2, -1), spec).keys,
    "segmented_multisplit": lambda k, spec, seg: core_multisplit.segmented_multisplit(
        k, spec, seg).keys,
    "histogram": lambda k, spec, seg: core_histogram.histogram(k, spec),
}


@pytest.mark.parametrize("entry", list(CORE_ENTRIES))
def test_core_entry_points_take_the_default(request, entry):
    """The core entry points resolve ``backend=None`` by the facade's rule:
    the compiled kernels on a TPU, the ``vmap`` stages without one."""
    keys = jnp.arange(N, dtype=jnp.uint32)
    seg = jnp.array([0, N // 2], jnp.int32)
    spec = ops.delta_buckets(16, N)
    # a fresh function per trace: jax caches the trace of a function object
    request.getfixturevalue("tpu")
    assert _kernels_in(lambda k: CORE_ENTRIES[entry](k, spec, seg), keys)
    request.getfixturevalue("no_tpu")
    assert not _kernels_in(lambda k: CORE_ENTRIES[entry](k, spec, seg), keys)


@pytest.mark.parametrize("sizes, kinds, backend", [
    ((4,), ("Auto",), "vmap"),                 # the partitioner would split a kernel
    ((1, 4), ("Auto", "Explicit"), "vmap"),
    ((4,), ("Manual",), "pallas"),             # inside shard_map: one device's part
    ((4, 2), ("Manual", "Auto"), "vmap"),      # shard_map over one axis of two
    ((1, 1), ("Auto", "Auto"), "pallas"),      # nothing to split
])
def test_under_an_auto_partitioned_mesh_the_default_is_vmap(tpu, sizes, kinds, backend):
    """Under a mesh whose partitioner would have to split the call (the MoE
    router's load count under the expert-parallel mesh), a Mosaic kernel
    cannot lower; inside ``shard_map`` it runs per device."""
    from jax.sharding import AbstractMesh, AxisType

    names = ("a", "b")[:len(sizes)]
    mesh = AbstractMesh(sizes, names, axis_types=tuple(getattr(AxisType, k) for k in kinds))
    with jax.sharding.use_abstract_mesh(mesh):
        assert default_backend(N + 5, jnp.int32) == backend
    reason = "auto-partitioned mesh" if backend == "vmap" else "tpu+32-bit keys"
    assert backend_decisions()[(N + 5, "int32")] == (backend, reason)
    assert default_backend(N + 5, jnp.int32) == "pallas"      # no mesh


def test_routing_and_serving_defaults_reach_the_kernels_on_a_tpu(tpu):
    from repro.models.moe import route_tokens_segmented

    ids = jnp.zeros((N,), jnp.int32)
    seg = jnp.array([0, 7, N // 2], jnp.int32)
    assert _kernels_in(lambda i: route_tokens_segmented(i, seg, 8, 64)[0], ids)
    assert ServingConfig().backend == "pallas"
    assert ServingConfig(backend="vmap").backend == "vmap"


def test_an_explicit_backend_is_honoured(tpu):
    keys = jnp.arange(N, dtype=jnp.uint32)
    spec = ops.delta_buckets(16, N)
    assert not _kernels_in(lambda k: ops.multisplit(k, spec, backend="vmap"), keys)
    assert not _kernels_in(lambda k: ops.radix_sort(k, backend="vmap")[0], keys)
    assert not _kernels_in(
        lambda k: ops.multisplit(k, spec, backend="reference").bucket_counts, keys)


def test_16_bit_keys_stay_on_vmap_through_the_facade(tpu):
    keys = jnp.arange(N, dtype=jnp.uint16)
    assert not _kernels_in(lambda k: ops.multisplit(k, ops.delta_buckets(4, N)), keys)
    assert not _kernels_in(lambda k: ops.radix_sort(k, key_bits=16)[0], keys)


def _op_span_stats(fn):
    """The stats of each ``repro.op`` span of one profiled eager call."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            jax.block_until_ready(fn())
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)[0]
        return [dict(e.stats) for plane in ProfileData.from_file(path).planes
                for line in plane.lines for e in line.events if e.name == "repro.op"]


@pytest.mark.parametrize("backend, auto", [(None, 1), ("vmap", 0), ("reference", 0)])
def test_the_op_span_names_the_backend_and_whether_the_default_chose_it(backend, auto):
    keys = jnp.arange(N, dtype=jnp.uint32)[::-1]
    spec = ops.delta_buckets(16, N)
    (stats,) = _op_span_stats(lambda: ops.multisplit(keys, spec, backend=backend))
    assert (stats["backend"], stats["auto"]) == (backend or "vmap", auto)
    (stats,) = _op_span_stats(lambda: ops.radix_sort(keys, backend=backend)[0])
    assert (stats["backend"], stats["auto"]) == (backend or "vmap", auto)
