"""The self-tuning layer (DESIGN.md §14): autotune-on-first-miss + a
persistent on-disk cache for every tuned decision.

PR 6 proved the hand-tuned flip points rot (two were measurably stale until
re-benched by hand).  This module closes the loop:

* **Opt-in.** ``repro.ops.set_autotune(True)`` (or ``REPRO_AUTOTUNE=1`` in
  the environment) arms the layer; by default every resolver keeps its
  heuristic and this module is inert — no timing, no disk I/O.
* **On-first-miss hooks.** When armed, a miss in ``_TILE_CACHE`` /
  ``_FAMILY_CACHE`` / ``_SUB_BITS_CACHE`` / ``_FUSION_CACHE`` first consults
  the persistent cache and otherwise runs the matching timing search
  (:func:`~repro.core.pipeline.tiles.autotune_tile` for the joint
  (tile, family) grid, :func:`autotune_fused2` for the fused-pair
  (tile, family, sub_bits) grid, :func:`autotune_label_fusion` for the vmap
  materialize-vs-fuse choice), pinning AND persisting the winner.
  Coherence rule: the FAMILY miss runs the JOINT search (family + tile pinned
  together); the TILE miss searches tiles constrained to the already-pinned
  family — so one ``make_plan`` can never mix a heuristic family with a tile
  tuned for a different one.
* **Persistence.** A single JSON file (atomic replace via tempfile +
  ``os.replace``, lazily loaded, best-effort — I/O failure never breaks a
  plan) keyed by ``(host fingerprint, kind, shape-class key)``; the
  shape-class key IS the in-memory cache key, so disk and memory can never
  disagree about identity.  ``SCHEMA_VERSION`` is embedded in the file; a
  bump (or any corruption) makes old files load as empty — clean heuristic
  fallback, never an error.
* **Search scope.** Timing searches need CONCRETE shapes: they never run
  under a jax trace (the label-fusion hook defers under tracing) and never
  reenter themselves (``_IN_SEARCH``).  Hook-triggered searches measure the
  flat shape class as a proxy for segmented/batched plans of equal scan
  width; :func:`~repro.core.pipeline.tiles.autotune_tile` accepts explicit
  ``segments=`` / ``batch=`` arguments to measure those layouts directly.
"""

from __future__ import annotations

import dataclasses
import json
import os
import platform
import tempfile
import time
from pathlib import Path
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

SCHEMA_VERSION = 1

_ENV_FLAG = "REPRO_AUTOTUNE"
_ENV_DIR = "REPRO_AUTOTUNE_DIR"


def _env_enabled() -> bool:
    return os.environ.get(_ENV_FLAG, "").strip().lower() in ("1", "true", "on")


@dataclasses.dataclass(frozen=True)
class AutotuneConfig:
    """The armed/disarmed state of the self-tuning layer.

    ``persist=None`` means "follow ``enabled``": the disk layer is active
    exactly when autotuning is — set ``persist=False`` to tune in memory
    only, or ``True`` to read/write the disk cache even while the on-miss
    searches stay off."""

    enabled: bool = False
    cache_dir: Optional[str] = None
    persist: Optional[bool] = None
    trials: int = 3
    candidates: Tuple[int, ...] = (256, 512, 1024, 2048, 4096)


_CONFIG = AutotuneConfig(enabled=_env_enabled())

# Reentrancy latch: the searches build plans/run resolvers themselves; while
# one is measuring, every hook is inert so candidate plans resolve through
# their EXPLICIT (tile, family, sub_bits) arguments only.
_IN_SEARCH = False

# Lazily-loaded snapshot of the disk file ({key_str: value}), or None when
# not yet read (drop_loaded() resets to None to simulate a fresh process).
_LOADED: Optional[dict] = None

_FINGERPRINT: Optional[str] = None


def set_autotune(enabled=None, *, cache_dir=None, persist=None, trials=None,
                 candidates=None):
    """Arm/disarm autotune-on-first-miss and configure the persistent cache.

    Every argument left ``None`` keeps its current value; returns the new
    :class:`AutotuneConfig` snapshot.  ``enabled=True`` makes cache misses
    in the (tile, family, sub_bits, label-fusion) resolvers consult the
    on-disk cache and otherwise run the timing search (DESIGN.md §14);
    ``cache_dir`` overrides where the JSON cache lives (default:
    ``$REPRO_AUTOTUNE_DIR`` or ``~/.cache/repro-multisplit``); ``trials`` /
    ``candidates`` bound the hook-triggered searches."""
    global _CONFIG, _LOADED
    kw = {}
    if enabled is not None:
        kw["enabled"] = bool(enabled)
    if cache_dir is not None:
        kw["cache_dir"] = str(cache_dir)
        _LOADED = None                      # re-read from the new location
    if persist is not None:
        kw["persist"] = bool(persist)
    if trials is not None:
        kw["trials"] = int(trials)
    if candidates is not None:
        kw["candidates"] = tuple(int(c) for c in candidates)
    _CONFIG = dataclasses.replace(_CONFIG, **kw)
    return _CONFIG


def autotune_status() -> dict:
    """Introspection: the active config, cache path, and entry count."""
    ent = _entries() if _persist_active() else {}
    return {
        "config": _CONFIG,
        "cache_path": str(cache_path()),
        "disk_entries": len(ent),
        "fingerprint": host_fingerprint(),
    }


def active() -> bool:
    """True when a miss may trigger a timing search right now."""
    return _CONFIG.enabled and not _IN_SEARCH


def armed() -> bool:
    """True when autotuning is opted in at all — even mid-search.  Cache-fill
    sites that would otherwise pin a HEURISTIC consult this to defer instead
    (an uncached heuristic answer keeps the shape measurable later)."""
    return _CONFIG.enabled


def _persist_active() -> bool:
    if _CONFIG.persist is not None:
        return _CONFIG.persist
    return _CONFIG.enabled


def host_fingerprint() -> str:
    """Stable per-host/per-accelerator identity for disk cache keys: tuned
    tiles are machine facts, not repo facts."""
    global _FINGERPRINT
    if _FINGERPRINT is None:
        try:
            dev = jax.devices()[0]
            accel = f"{dev.platform}-{dev.device_kind}"
        except Exception:                   # pragma: no cover - no backend
            accel = "unknown"
        raw = f"{platform.machine()}-{accel}"
        _FINGERPRINT = raw.replace(" ", "_").replace("|", "_")
    return _FINGERPRINT


def cache_path() -> Path:
    base = _CONFIG.cache_dir or os.environ.get(_ENV_DIR) or os.path.join(
        os.path.expanduser("~"), ".cache", "repro-multisplit"
    )
    return Path(base) / "multisplit_autotune.json"


def _key_str(kind: str, mem_key: Tuple) -> str:
    """Disk key = fingerprint | kind | the in-memory cache key, verbatim —
    disk and memory can never disagree about a shape class's identity."""
    parts = "|".join(str(x) for x in mem_key)
    return f"{host_fingerprint()}|{kind}|{parts}"


def _entries() -> dict:
    """The lazily-loaded disk snapshot. Missing / unreadable / corrupt /
    stale-version files all load as EMPTY — heuristic fallback, never an
    error (regression-tested)."""
    global _LOADED
    if _LOADED is None:
        _LOADED = {}
        try:
            with open(cache_path()) as f:
                raw = json.load(f)
            if (isinstance(raw, dict)
                    and raw.get("version") == SCHEMA_VERSION
                    and isinstance(raw.get("entries"), dict)):
                _LOADED = dict(raw["entries"])
        except (OSError, ValueError):
            pass
    return _LOADED


def _flush(entries: dict) -> None:
    """Atomic write: tempfile in the target dir + ``os.replace`` — a reader
    never observes a torn file. Best-effort: an unwritable dir silently
    degrades to memory-only tuning."""
    path = cache_path()
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=str(path.parent), prefix=".autotune-")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"version": SCHEMA_VERSION, "entries": entries},
                          f, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except OSError:
        pass


def record(kind: str, mem_key: Tuple, value) -> None:
    """Persist one tuned decision (no-op while the disk layer is off)."""
    if not _persist_active():
        return
    ent = _entries()
    ent[_key_str(kind, mem_key)] = value
    _flush(ent)


def lookup(kind: str, mem_key: Tuple):
    """Read one persisted decision, or None (disk layer off / no entry)."""
    if not _persist_active():
        return None
    return _entries().get(_key_str(kind, mem_key))


def drop_loaded() -> None:
    """Forget the in-process snapshot; the next lookup re-reads the file
    (what a fresh process would see)."""
    global _LOADED
    _LOADED = None


def clear_disk() -> None:
    """Delete the on-disk cache layer (and the loaded snapshot)."""
    global _LOADED
    _LOADED = {}
    try:
        os.remove(cache_path())
    except OSError:
        pass


_DISK_REASON = "autotuned (persistent cache hit)"


# ---------------------------------------------------------------------------
# On-first-miss hooks (called by the resolvers in tiles.py / spec.py)
# ---------------------------------------------------------------------------

def _pair_geometry(pair_m: int, stage_m: int) -> Optional[Tuple[int, int]]:
    """(bits, split) of a fused pair from the hook's (pair_m, stage_m)
    hints, or None when the widths aren't pure powers of two (segmented
    multiples): then the measured search has no derivable schedule and the
    heuristic stands."""
    if pair_m <= 0 or stage_m <= 0:
        return None
    if pair_m & (pair_m - 1) or stage_m & (stage_m - 1):
        return None
    bits = pair_m.bit_length() - 1
    split = stage_m.bit_length() - 1
    if not 0 < split < bits:
        return None
    return bits, split


def maybe_tune_family(
    n: int, m: int, method: str, backend: str, *,
    digits: int = 1, key_value: bool = False, pair_m: Optional[int] = None,
) -> None:
    """Family-cache miss: disk hit pins the family; otherwise run the JOINT
    search so the family and its tile are pinned together (never a heuristic
    family with a tuned tile for another)."""
    global _IN_SEARCH
    if not _CONFIG.enabled or _IN_SEARCH:
        return
    from repro.core.pipeline import tiles as _t
    from repro.core.pipeline.registry import get_backend

    fkey = _t._family_key(n, m, method, backend, digits)
    fam = lookup("family", fkey)
    if fam is not None:
        _t._FAMILY_CACHE[fkey] = (str(fam), _DISK_REASON)
        return
    if not get_backend(backend).tiled:
        return                              # untiled oracle: nothing to tune
    _IN_SEARCH = True
    try:
        if digits == 1:
            from repro.core.identifiers import EvenSpec

            _t.autotune_tile(
                n, EvenSpec(0.0, float(1 << 30), m), method=method,
                key_value=key_value, backend=backend,
                candidates=_CONFIG.candidates, trials=_CONFIG.trials,
            )
        else:
            geom = _pair_geometry(pair_m or 0, m)
            if geom is None:
                return
            bits, split = geom
            autotune_fused2(
                n, 0, bits, split, method=method, key_value=key_value,
                backend=backend, trials=_CONFIG.trials,
            )
    finally:
        _IN_SEARCH = False


def maybe_tune_tile(
    n: int, m: int, method: str, key_value: bool, backend: str, *,
    digits: int = 1, stage_m: Optional[int] = None,
    family: Optional[str] = None,
) -> None:
    """Tile-cache miss (family already resolved): disk hit pins the tile;
    otherwise search tiles CONSTRAINED to the resolved family."""
    global _IN_SEARCH
    if not _CONFIG.enabled or _IN_SEARCH:
        return
    from repro.core.pipeline import tiles as _t
    from repro.core.pipeline.registry import get_backend

    tkey = _t._tile_key(n, m, method, key_value, backend, digits, stage_m)
    tile = lookup("tile", tkey)
    if tile is not None:
        _t._TILE_CACHE[tkey] = int(tile)
        return
    if not get_backend(backend).tiled:
        return
    families = None if family is None else (family,)
    _IN_SEARCH = True
    try:
        if digits == 1:
            from repro.core.identifiers import EvenSpec

            _t.autotune_tile(
                n, EvenSpec(0.0, float(1 << 30), m), method=method,
                key_value=key_value, backend=backend, families=families,
                candidates=_CONFIG.candidates, trials=_CONFIG.trials,
            )
        else:
            geom = _pair_geometry(m, stage_m or 0)
            if geom is None:
                return
            bits, split = geom
            autotune_fused2(
                n, 0, bits, split, method=method, key_value=key_value,
                backend=backend, families=families, trials=_CONFIG.trials,
            )
    finally:
        _IN_SEARCH = False


def maybe_tune_sub_bits(
    n: int, m: int, method: str, key_value: bool, backend: str, stage_m: int,
) -> None:
    """Sub-bits miss: disk-only — the fused-pair joint search
    (:func:`autotune_fused2`, reached through the family/tile hooks) is what
    MEASURES sub_bits; this hook only rehydrates a persisted pin."""
    if not _CONFIG.enabled:
        return
    from repro.core.pipeline import tiles as _t

    key = (n, m, method, key_value, backend, stage_m)
    val = lookup("sub_bits", key)
    if val is not None:
        _t._SUB_BITS_CACHE[key] = int(val)


def maybe_tune_fusion(spec):
    """Label-fusion miss on the generic vmap path: disk hit, else time the
    materialize-vs-fuse pair on synthetic keys of the plan's own shape.
    Returns the pinned ``(fused?, reason)`` or None (disarmed / in-search /
    underivable). Caller guarantees keys are NOT traced."""
    global _IN_SEARCH
    if not _CONFIG.enabled or _IN_SEARCH:
        return None
    from repro.core.pipeline import spec as _sp

    key = (spec.backend, type(spec.bucket_fn).__name__, spec.m_eff)
    val = lookup("fusion", key)
    if val is not None:
        hit = (bool(val), _DISK_REASON)
        _sp._FUSION_CACHE[key] = hit
        return hit
    _IN_SEARCH = True
    try:
        hit = autotune_label_fusion(spec, trials=_CONFIG.trials)
    finally:
        _IN_SEARCH = False
    return hit


# ---------------------------------------------------------------------------
# The measured searches for the PR-7 axes (label fusion, fused-pair grid)
# ---------------------------------------------------------------------------

def _time_once(fn, args, trials: int) -> float:
    jax.block_until_ready(fn(*args))        # compile
    ts = []
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _synthetic_call(spec, seed: int = 0):
    """(jitted runner, concrete args) exercising one plan end to end."""
    import numpy as np

    rng = np.random.RandomState(seed)
    shape = (spec.batch, spec.n) if spec.batch is not None else (spec.n,)
    keys = jnp.asarray(rng.randint(0, 1 << 30, shape, dtype=np.uint32))
    args = [keys]
    if spec.key_value:
        args.append(jnp.arange(keys.size, dtype=jnp.int32).reshape(shape))
    if spec.segments is not None:
        starts = (jnp.arange(spec.segments, dtype=jnp.int32) * spec.n
                  ) // spec.segments
        run = jax.jit(lambda *a: spec(*a, segment_starts=starts).keys
                      if spec.mode == "reorder"
                      else spec(*a, segment_starts=starts).bucket_counts)
    else:
        run = jax.jit(lambda *a: spec(*a).keys if spec.mode == "reorder"
                      else spec(*a).bucket_counts)
    return run, tuple(args)


def autotune_label_fusion(spec, *, trials: int = 3, seed: int = 0):
    """Time the plan with label fusion forced ON vs OFF (by pre-pinning the
    fusion cache around two runs), pin + persist the winner with the losing
    time in the reason. Returns the pinned ``(fused?, reason)``."""
    from repro.core.pipeline import spec as _sp

    bf = spec.bucket_fn
    if bf is None or not bf.fusable:
        return None
    key = (spec.backend, type(bf).__name__, spec.m_eff)
    times = {}
    try:
        for fused in (True, False):
            _sp._FUSION_CACHE[key] = (fused, "autotune probe")
            run, args = _synthetic_call(spec, seed=seed)
            times[fused] = _time_once(run, args, trials)
    finally:
        _sp._FUSION_CACHE.pop(key, None)
    win = times[True] <= times[False]
    hit = (win, (
        f"autotuned: fused {times[True]:.3e}s vs materialized "
        f"{times[False]:.3e}s at m_eff={spec.m_eff} on {spec.backend!r}"
    ))
    _sp._FUSION_CACHE[key] = hit
    record("fusion", key, bool(win))
    return hit


def autotune_fused2(
    n: int,
    shift: int,
    bits: int,
    split: int,
    *,
    method: str = "bms",
    key_value: bool = False,
    backend: str = "vmap",
    candidates: Tuple[int, ...] = (1024, 2048, 4096, 8192),
    families: Optional[Tuple[str, ...]] = None,
    sub_bits_candidates: Tuple[int, ...] = (2, 4, 8),
    trials: int = 3,
    seed: int = 0,
) -> Optional[Tuple[int, str, int]]:
    """Joint (tile, family, sub_bits) timing search over ONE fused-pair
    radix sweep (DESIGN.md §13/§14): the pair footprint axes the digits=1
    search cannot see. Pins the digits=2 tile/family entries and the
    per-shape sub-bits width, persists all three, and returns the winning
    ``(tile, family, sub_bits)`` (None when nothing ran)."""
    import numpy as np

    from repro.core.pipeline import tiles as _t
    from repro.core.pipeline.registry import get_backend
    from repro.core.pipeline.spec import make_radix_plan

    be = get_backend(backend)
    if not be.tiled or not be.fuses_digits:
        return None
    if families is None:
        families = be.families
    m2 = 1 << bits
    stage_m = 1 << split
    rng = np.random.RandomState(seed)
    keys = jnp.asarray(rng.randint(0, 1 << 31, n, dtype=np.uint32))
    values = jnp.arange(n, dtype=jnp.int32) if key_value else None
    args = (keys, values) if key_value else (keys,)
    best = None
    for tile in candidates:
        if tile > max(n, _t._MIN_TILE):
            continue
        for fam in families:
            for sb in sub_bits_candidates:
                if not 0 < sb <= bits:
                    continue
                plan = make_radix_plan(
                    n, shift, bits, method=method, key_value=key_value,
                    backend=backend, tile=tile, family=fam,
                    digit_split=split, sub_bits=sb,
                )
                run = (jax.jit(lambda k, v, p=plan: p(k, v).keys) if key_value
                       else jax.jit(lambda k, p=plan: p(k).keys))
                t = _time_once(run, args, trials)
                if best is None or t < best[0]:
                    best = (t, tile, fam, sb)
    if best is None:
        return None
    t_best, tile_b, fam_b, sb_b = best
    tkey = _t._tile_key(n, m2, method, key_value, backend, 2, stage_m)
    _t._TILE_CACHE[tkey] = tile_b
    _t._TILE_CACHE.pop(
        _t._tile_key(n, m2, method, not key_value, backend, 2, stage_m), None
    )
    fkey = _t._family_key(n, stage_m, method, backend, 2)
    _t._FAMILY_CACHE[fkey] = (fam_b, (
        f"autotuned over fused-pair grid tiles={tuple(candidates)} x "
        f"families={tuple(families)} x sub_bits={tuple(sub_bits_candidates)}: "
        f"({tile_b}, {fam_b!r}, {sb_b}) won at {t_best:.3e}s"
    ))
    sbkey = (n, m2, method, key_value, backend, stage_m)
    _t._SUB_BITS_CACHE[sbkey] = sb_b
    record("tile", tkey, tile_b)
    record("family", fkey, fam_b)
    record("sub_bits", sbkey, sb_b)
    return tile_b, fam_b, sb_b
