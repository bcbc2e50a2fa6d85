"""Host spans for the profiler, with the compile-path work counted on each.

``span(name, **stats)`` opens a ``jax.profiler.TraceAnnotation`` while a
profiler is collecting, so the span lands on the calling thread's host line of
the same trace, and on the same clock, as the device programs launched inside
it. While it is the innermost open span of its thread, the compile-path
events that JAX reports through ``jax.monitoring`` are counted on it and
written as stats when it closes (zeros are left out):

* ``traces``: jaxprs traced for a ``jit`` (``jaxpr_trace_duration``);
* ``lowerings``: jaxprs lowered to a module (``jaxpr_to_mlir_module_duration``);
* ``compiles``: executables compiled or read from the persistent compile
  cache (``backend_compile_duration``, which wraps both);
* ``cache_reads``: of those, the ones read from the cache
  (``cache_retrieval_time_sec``);
* ``compile_ms``: the time of the traces, lowerings and compiles. A cache
  read lies inside its compile and is not added again; a ``jit`` traced
  inside another's trace is timed in both.

``count(**amounts)`` adds the program's own counters to the innermost open
span the same way (the exchange's ``exchange_shipped_bytes`` and
``exchange_payload_bytes``).

With no profiler collecting, ``span`` returns one shared context that does
nothing: no annotation, no stack, no counting. Under a JAX transformation
(``jit``, ``vmap``, ``grad``) a span times the tracing only, and changes no
jaxpr.

``run_staged(fn, *args)`` runs ``fn`` eagerly the way a ``custom_vmap`` op
does: traced to a jaxpr (the span ``repro.trace``), then evaluated equation
by equation, each intermediate freed after its last use. While a profiler collects, a span that
``fn`` opens as it is traced becomes a pair of marks in the jaxpr, and the
span is open while the equations between them are evaluated, so that it holds
the device programs they launch.
"""

from __future__ import annotations

import threading

import jax
from jax.extend.core import Primitive
from jax.profiler import TraceAnnotation

# monitoring event -> the stat that counts it on the innermost open span
_COUNTED = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
    "/jax/core/compile/backend_compile_duration": "compiles",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_reads",
}
_TIMED = ("traces", "lowerings", "compiles")

_local = threading.local()


def enabled() -> bool:
    """Whether a profiler is collecting, so that spans are recorded."""
    return TraceAnnotation.is_enabled()


class _Off:
    """The span while no profiler collects: enters, exits and records
    nothing. False in a condition, so that a caller can skip working out
    stats that would go nowhere."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def set(self, **stats) -> None:
        pass


OFF = _Off()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("_annotation", "_late", "counts")

    def __init__(self, name: str, stats: dict):
        self._annotation = TraceAnnotation(name, **stats)
        self._late: dict = {}
        self.counts: dict = {}

    def set(self, **stats) -> None:
        """Stats known only once the span's work is done; written at close."""
        self._late.update(stats)

    def __enter__(self):
        self._annotation.__enter__()
        _stack().append(self)
        return self

    def __exit__(self, *exc):
        _stack().pop()
        meta = {k: v for k, v in self._late.items() if v is not None}
        meta.update((k, v) for k, v in self.counts.items() if v)
        if meta:
            self._annotation.set_metadata(**meta)
        return self._annotation.__exit__(*exc)


def span(name: str, **stats):
    """A context manager: the span ``name`` with ``stats`` (``None`` values
    left out) while a profiler collects, else :data:`OFF`. While
    :func:`run_staged` traces, the marks that open and close it."""
    if not TraceAnnotation.is_enabled():
        return OFF
    stats = {k: v for k, v in stats.items() if v is not None}
    if getattr(_local, "staging", False):
        return _Marks(name, stats)
    return _Span(name, stats)


# A mark opens the span ``name`` with ``stats`` when it is evaluated, or, with
# ``name=None``, closes the last one opened.
_mark_p = Primitive("repro_span_mark")
_mark_p.multiple_results = True
_mark_p.def_abstract_eval(lambda **_: [])


@_mark_p.def_impl
def _mark(*, name, stats):
    if name is None:
        _local.marks.pop().__exit__(None, None, None)
    else:
        ctx = span(name, **dict(stats))
        ctx.__enter__()
        _local.marks.append(ctx)
    return []


class _Marks:
    """A span while :func:`run_staged` traces: a mark at entry and at exit."""

    __slots__ = ("_name", "_stats")

    def __init__(self, name: str, stats: dict):
        self._name, self._stats = name, tuple(stats.items())

    def __enter__(self):
        _mark_p.bind(name=self._name, stats=self._stats)
        return self

    def __exit__(self, *exc):
        _mark_p.bind(name=None, stats=())
        return False


def run_staged(fn, *args):
    """``fn(*args)`` on concrete arrays, traced to a jaxpr inside the span
    ``repro.trace`` and then evaluated (:func:`jax.core.eval_jaxpr`), with
    the spans ``fn`` opens open while their equations run."""
    with span("repro.trace"):
        _local.staging = True
        try:
            closed, shape = jax.make_jaxpr(lambda *a: fn(*a), return_shape=True)(*args)
        finally:
            _local.staging = False
    _local.marks = []
    try:
        out = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *jax.tree.leaves(args))
    finally:
        while _local.marks:                     # closed early by an error
            _local.marks.pop().__exit__(None, None, None)
    return jax.tree.unflatten(jax.tree.structure(shape), out)


def count(**amounts) -> None:
    """Add ``amounts`` to the counters of the innermost open span, written
    as its stats when it closes; nothing while no span is open. Called
    while a ``jit`` traces, it counts once per trace, not once per call."""
    stack = getattr(_local, "stack", None)
    if not stack:
        return
    counts = stack[-1].counts
    for key, amount in amounts.items():
        counts[key] = counts.get(key, 0) + amount


def _on_event(event: str, secs: float, **_kw) -> None:
    stack = getattr(_local, "stack", None)
    if not stack:
        return
    key = _COUNTED.get(event)
    if key is None:
        return
    counts = stack[-1].counts
    counts[key] = counts.get(key, 0) + 1
    if key in _TIMED:
        counts["compile_ms"] = counts.get("compile_ms", 0.0) + secs * 1e3


jax.monitoring.register_event_duration_secs_listener(_on_event)
