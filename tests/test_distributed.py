"""Distributed multisplit over a mesh axis (runs subprocesses with virtual
devices: the main pytest process must keep seeing exactly 1 CPU device)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import distributed
from repro.core.identifiers import delta_buckets
from repro.core.pipeline import backend_decisions
from repro.kernels import ops as kops

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_with_devices(n_devices: int, body: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    code = textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=600
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


@pytest.mark.slow
def test_multisplit_sharded_equal_shards():
    out = _run_with_devices(8, """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed import make_multisplit_sharded
        from repro.core.multisplit import multisplit_ref
        from repro.core.identifiers import delta_buckets
        mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
        for m in (2, 11, 64, 256):
            rng = np.random.RandomState(m)
            keys = jnp.asarray(rng.randint(0, 2**30, 8 * 512, dtype=np.uint32))
            vals = jnp.arange(keys.shape[0], dtype=jnp.int32)
            bf = delta_buckets(m, 2**30)
            with jax.set_mesh(mesh):
                f = make_multisplit_sharded(bf, mesh, "x", key_value=True)
                out = f(keys, vals)
            ref = multisplit_ref(keys, bf, vals)
            assert np.array_equal(np.asarray(out.keys), np.asarray(ref.keys)), m
            assert np.array_equal(np.asarray(out.values), np.asarray(ref.values)), m
            assert np.array_equal(np.asarray(out.bucket_counts), np.asarray(ref.bucket_counts)), m
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_multisplit_bucket_sharded():
    out = _run_with_devices(8, """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core.distributed import multisplit_bucket_sharded, BucketShardedResult
        from repro.core.multisplit import multisplit_ref
        from repro.core.identifiers import delta_buckets
        D = 8
        mesh = jax.make_mesh((D,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
        for m in (8, 64, 256):
            rng = np.random.RandomState(m)
            n = D * 256
            cap = 2 * n // D
            keys = jnp.asarray(rng.randint(0, 2**30, n, dtype=np.uint32))
            vals = jnp.arange(n, dtype=jnp.int32)
            bf = delta_buckets(m, 2**30)
            fn = lambda k, v: multisplit_bucket_sharded(k, bf, v, axis_name="x", capacity=cap)
            f = jax.shard_map(fn, mesh=mesh, in_specs=(P("x"), P("x")),
                out_specs=BucketShardedResult(P("x"), P("x"), P("x"), P("x"), P()),
                check_vma=False)
            with jax.set_mesh(mesh):
                out = f(keys, vals)
            ref = multisplit_ref(keys, bf, vals)
            ko = np.asarray(out.keys).reshape(D, cap)
            cnt = np.asarray(out.count).reshape(D)
            rk = np.concatenate([ko[d, :cnt[d]] for d in range(D)])
            assert np.array_equal(rk, np.asarray(ref.keys)), m
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_one_cell_both_meshes():
    """End-to-end: the real dryrun driver — multi-pod compile proof + the
    single-pod roofline accounting."""
    out = _run_with_devices(512, """
        from repro.launch.dryrun import lower_cell
        rec = lower_cell("xlstm-350m", "decode_32k", "multi")
        assert rec["status"] == "ok", rec
        assert rec["n_chips"] == 512
        assert rec["compile_s"] > 0            # pod-axis shard proof
        rec1 = lower_cell("xlstm-350m", "decode_32k", "single")
        assert rec1["status"] == "ok", rec1
        assert rec1["hlo_flops"] > 0 and rec1["collective_bytes"] >= 0
        print("OK", rec1["dominant"])
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# The sharded key-value multisplit with default arguments: the local stage
# takes the platform default, as the ``repro.ops`` facade does.
# ---------------------------------------------------------------------------

SHARD = 4096
KV_CASES = [(m, be) for m in (2, 256) for be in ("default", "pallas-interpret")]
# Inputs that shape the exchange's send matrix, on the default backend:
# "one_bucket" puts every key in one bucket (the matrix is diagonal, most
# runs empty); "skewed" draws three shards' keys from the lowest eighth of
# the range, so buckets straddle chip boundaries.
EDGE_CASES = [(m, dist) for dist in ("one_bucket", "skewed") for m in (2, 16, 256)]
EDGE_CASES.append((16, "uniform"))


@pytest.fixture(scope="module")
def sharded_kv_results():
    """Every case of ``make_multisplit_sharded(spec, mesh, "x",
    key_value=True)`` at 4 x 4096 pairs, in one child with four CPU
    devices: ``(m, backend, keys) -> which fields agree with
    multisplit_ref``."""
    cases = [(m, be, "uniform") for m, be in KV_CASES]
    cases += [(m, "default", dist) for m, dist in EDGE_CASES]
    out = _run_with_devices(4, f"""
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed import make_multisplit_sharded
        from repro.core.identifiers import delta_buckets
        from repro.core.multisplit import multisplit_ref
        from repro.core.pipeline import backend_decisions
        D, n_shard = 4, {SHARD}
        mesh = jax.make_mesh((D,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
        for m, be, dist in {cases!r}:
            keys = jax.random.bits(jax.random.key(m), (D * n_shard,), jnp.uint32)
            if dist == "one_bucket":
                keys = jnp.full_like(keys, 3 * 2**30 + 12345)
            elif dist == "skewed":
                keys = keys.at[:3 * n_shard].set(keys[:3 * n_shard] >> 3)
            vals = jnp.arange(D * n_shard, dtype=jnp.uint32)
            spec = delta_buckets(m, 2**32)
            kw = {{}} if be == "default" else {{"backend": be}}
            got = jax.jit(make_multisplit_sharded(spec, mesh, "x", key_value=True, **kw))(keys, vals)
            want = multisplit_ref(keys, spec, vals)
            row = {{f: bool(np.array_equal(np.asarray(getattr(got, f)), np.asarray(getattr(want, f))))
                    for f in ("keys", "values", "bucket_counts", "bucket_starts")}}
            row["placed"] = all(
                sorted((s.index[0].start or 0, s.data.shape[0]) for s in a.addressable_shards
                       if s.device == dev) == [(d * n_shard, n_shard)]
                for a in (got.keys, got.values) for d, dev in enumerate(mesh.devices.flat))
            row["decision"] = backend_decisions().get((n_shard, "uint32"))
            print(json.dumps({{"case": [m, be, dist], **row}}))
    """)
    rows = [json.loads(x) for x in out.splitlines() if x.startswith("{")]
    return {tuple(r.pop("case")): r for r in rows}


AGREE = {"keys": True, "values": True, "bucket_counts": True, "bucket_starts": True,
         "placed": True, "decision": ["vmap", "no TPU"]}


@pytest.mark.parametrize("m, backend", KV_CASES)
def test_sharded_key_value_with_default_arguments(sharded_kv_results, m, backend):
    row = sharded_kv_results[(m, backend, "uniform")]
    assert row == AGREE, row


@pytest.mark.parametrize("m, dist", EDGE_CASES)
def test_sharded_exchange_edge_cases(sharded_kv_results, m, dist):
    """Empty runs, a diagonal send matrix and buckets that straddle chips
    give the reference's result, each chip holding its own global slice."""
    row = sharded_kv_results[(m, "default", dist)]
    assert row == AGREE, row


def _mesh1():
    return jax.make_mesh((1,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))


def _plan_backends(monkeypatch, entry, **kw):
    """The backend of every plan the sharded ``entry`` builds, traced on a
    one-device mesh (nothing is run)."""
    seen = []
    make_plan = distributed.make_plan

    def spy(*args, **plan_kw):
        seen.append(plan_kw["backend"])
        return make_plan(*args, **plan_kw)

    monkeypatch.setattr(distributed, "make_plan", spy)
    keys = jnp.arange(SHARD, dtype=jnp.uint32) * 977
    spec = delta_buckets(8, 2**32)
    mesh = _mesh1()
    if entry == "multisplit_sharded":
        fn = distributed.make_multisplit_sharded(spec, mesh, "x", key_value=True, **kw)
    elif entry == "multisplit_bucket_sharded":
        fn = jax.shard_map(
            lambda k, v: distributed.multisplit_bucket_sharded(
                k, spec, v, axis_name="x", capacity=SHARD, **kw),
            mesh=mesh, in_specs=(P("x"), P("x")),
            out_specs=distributed.BucketShardedResult(P("x"), P("x"), P("x"), P("x"), P()),
            check_vma=False)
    else:
        fn = lambda k, v: distributed.multisplit_all_shards(k[None], spec, v[None], **kw)
    jaxpr = str(jax.make_jaxpr(fn)(keys, keys))
    return seen, "pallas_call" in jaxpr


ENTRIES = ["multisplit_sharded", "multisplit_bucket_sharded", "multisplit_all_shards"]


@pytest.fixture
def tpu(monkeypatch):
    """A TPU attached, REPRO_INTERPRET unset: kernels trace compiled."""
    monkeypatch.setattr(kops, "_tpu_available", lambda: True)
    monkeypatch.delenv("REPRO_INTERPRET", raising=False)


@pytest.mark.parametrize("entry", ENTRIES)
def test_sharded_call_naming_no_backend_asks_the_default(tpu, monkeypatch, entry):
    seen, kernels = _plan_backends(monkeypatch, entry)
    plans = 1 if entry == "multisplit_all_shards" else 2   # local stage, receiver's
    assert seen == ["pallas"] * plans and kernels
    assert backend_decisions()[(SHARD, "uint32")] == ("pallas", "tpu+32-bit keys")
    if entry == "multisplit_bucket_sharded":       # the positions-only plan
        assert backend_decisions()[(SHARD, "int32")] == ("pallas", "tpu+32-bit keys")


@pytest.mark.parametrize("entry", ENTRIES)
def test_sharded_call_on_this_host_defaults_to_vmap(monkeypatch, entry):
    seen, kernels = _plan_backends(monkeypatch, entry)
    assert set(seen) == {"vmap"} and not kernels


@pytest.mark.parametrize("backend", ["pallas", "vmap", "pallas-interpret"])
@pytest.mark.parametrize("entry", ENTRIES)
def test_a_named_backend_is_honoured(tpu, monkeypatch, entry, backend):
    """A backend the caller names runs every local stage, on a TPU too."""
    seen, kernels = _plan_backends(monkeypatch, entry, backend=backend)
    assert set(seen) == {backend}
    assert kernels == (backend != "vmap")


@pytest.mark.parametrize("transport", ["ragged", "sparse"])
def test_multisplit_sharded_refuses_a_transport_it_lacks(transport):
    keys = jnp.arange(SHARD, dtype=jnp.uint32)
    spec = delta_buckets(8, 2**32)
    with pytest.raises(ValueError, match="transport='dense' only"):
        distributed.multisplit_sharded(keys, spec, axis_name="x", transport=transport)
    fn = distributed.make_multisplit_sharded(spec, _mesh1(), "x", transport=transport)
    with pytest.raises(ValueError, match="'dense'"):
        jax.jit(fn)(keys)
