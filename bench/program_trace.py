"""The program's own host spans in a profiler trace, and the device programs
each one launched: :class:`ProgramTrace` extends :class:`bench.trace.Trace`
and changes nothing that it reads.

The program (``repro.runtime.tracing``) opens spans named ``repro.*`` on the
thread that calls it (``repro.op``, ``repro.dispatch``, ``repro.plan``,
``repro.trace``, ``repro.stage.<stage>``, ``repro.sort.pass``), each with
stats: its arguments, and the traces, lowerings, compiles and compile-cache reads that
arrived while it was the innermost one, with their ``compile_ms``.

Each run of a device program is tied to the moment it was launched by flow
ids. As a v5e writes them (read by hand, JAX 0.9): an event of the chip's
``XLA Modules`` line carries ``_c`` with flow type ``_ct`` 12; the host event
``DoEnqueueProgram`` carries the same id as ``_p`` (``_pt`` 12). That event
lies inside a host event that carries a flow id of its own as a consumer,
whose producer lies inside another, and so on: ``PJRT_LoadedExecutable_Execute``
(``_c``, type 14) on the thread that enqueued it, or, on a task thread,
``tpu::System::Execute=>IssueSequencedEvent`` (``_c``, type 7), produced by
``tpu::System::Execute`` inside ``PJRT_LoadedExecutable_Execute``. The chain
ends on the Python thread, at ``PJRT_LoadedExecutable_Execute linkage``
(``_p``, type 14): the launch. Ids repeat across flow types, so an id is
matched together with its type.

A run, and the ``XLA Ops`` inside it, belong to the innermost ``repro.``
span open at its launch. Stages that run inside one jitted program are not
told apart: the v5e trace names no operation after the span that traced it.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from bench import trace as base

PROGRAM_PREFIX = "repro."
STAGE_SPANS = tuple(f"repro.stage.{s}"
                    for s in ("layout", "prescan", "scan", "postscan", "scatter"))
# JAX's own spans on the calling thread while it lowers or compiles a program.
COMPILE_SPANS = ("lower_sharding_computation", "backend_compile_and_load")
FLOW_STATS = ("_p", "_pt", "_c", "_ct")
ENQUEUE_FLOW = 12           # flow type from a host enqueue to a device run


@dataclasses.dataclass(frozen=True)
class ProgramSpan:
    name: str
    start: int
    end: int
    stats: Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Launch:
    """One run of a device program."""

    device: int
    program: str                 # "jit_scan"
    start: int
    end: int
    busy_ns: int                 # union of its ops' intervals
    launched_at: Optional[int]   # on the Python thread; None where no chain
    span: Optional[str]          # innermost program span open then


def _stats(ev) -> Dict[str, Any]:
    return dict(ev.stats)


class _Flows:
    """The host events that carry flow ids, by line."""

    def __init__(self, lines: Sequence[Any]):
        self.producers: Dict[Tuple[int, int], Tuple[int, int, int]] = {}
        self.consumers: List[List[Tuple[int, int, int, int]]] = []
        for i, line in enumerate(lines):
            cons = []
            for ev in line.events:
                st = _stats(ev)
                if not any(k in st for k in FLOW_STATS):
                    continue
                s, e = int(ev.start_ns), int(ev.end_ns)
                if "_p" in st:
                    self.producers[(int(st.get("_pt", 0)), int(st["_p"]))] = (i, s, e)
                if "_c" in st:
                    cons.append((s, e, int(st.get("_ct", 0)), int(st["_c"])))
            cons.sort()
            self.consumers.append(cons)
        self.starts = [[c[0] for c in cons] for cons in self.consumers]

    def _enclosing(self, line: int, s: int, e: int) -> Optional[Tuple[int, int]]:
        """``(type, id)`` of the innermost consumer event on ``line`` that
        holds ``[s, e]``."""
        cons = self.consumers[line]
        for j in range(bisect.bisect_right(self.starts[line], s) - 1, -1, -1):
            cs, ce, ct, c = cons[j]
            if ce >= e:
                return ct, c
        return None

    def launch_time(self, flow: Tuple[int, int], target: int) -> Optional[int]:
        """Start of the event on line ``target`` that the chain from the
        consumer ``flow`` leads to."""
        seen = set()
        while flow not in seen:
            seen.add(flow)
            hit = self.producers.get(flow)
            if hit is None:
                return None
            line, s, e = hit
            if line == target:
                return s
            flow = self._enclosing(line, s, e)
            if flow is None:
                return None
        return None


def _owner(spans: List[ProgramSpan], times: List[int]) -> Dict[int, str]:
    """The innermost span (spans sorted by start, nested) open at each time."""
    out: Dict[int, str] = {}
    stack: List[ProgramSpan] = []
    i = 0
    for t in sorted(set(times)):
        while i < len(spans) and spans[i].start <= t:
            while stack and stack[-1].end <= spans[i].start:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        if stack:
            out[t] = stack[-1].name
    return out


@dataclasses.dataclass
class ProgramTrace(base.Trace):
    program_spans: List[ProgramSpan] = dataclasses.field(default_factory=list)
    compile_spans: List[base.Span] = dataclasses.field(default_factory=list)
    launches: Dict[int, List[Launch]] = dataclasses.field(default_factory=dict)

    @classmethod
    def from_profile(cls, data) -> "ProgramTrace":
        tr = super().from_profile(data)
        host = next((p for p in data.planes if p.name == base.HOST_PLANE), None)
        lines = list(host.lines) if host is not None else []
        # the Python thread: the line of the benchmark's and program's spans
        target = next((i for i, line in enumerate(lines) if any(
            ev.name.startswith((base.SPAN_PREFIX, PROGRAM_PREFIX))
            for ev in line.events)), None)
        if target is not None:
            for ev in lines[target].events:
                s, e = int(ev.start_ns), int(ev.end_ns)
                if ev.name.startswith(PROGRAM_PREFIX):
                    st = {k: v for k, v in _stats(ev).items() if k not in FLOW_STATS}
                    tr.program_spans.append(ProgramSpan(ev.name, s, e, st))
                elif ev.name in COMPILE_SPANS:
                    tr.compile_spans.append(base.Span(ev.name, s, e))
        tr.program_spans.sort(key=lambda sp: (sp.start, -sp.end))
        tr.compile_spans.sort(key=lambda sp: sp.start)

        flows = _Flows(lines)
        runs = []                      # (device, program, start, end, launched_at)
        for plane in data.planes:
            m = base.DEVICE_PLANE.match(plane.name)
            if not m:
                continue
            for line in plane.lines:
                if line.name != base.MODULES_LINE:
                    continue
                for ev in line.events:
                    st = _stats(ev)
                    at = None
                    if target is not None and "_c" in st:
                        at = flows.launch_time(
                            (int(st.get("_ct", ENQUEUE_FLOW)), int(st["_c"])), target)
                    runs.append((int(m.group(1)), base.program_name(ev.name),
                                 int(ev.start_ns), int(ev.end_ns), at))
        owner = _owner(tr.program_spans, [r[4] for r in runs if r[4] is not None])
        starts = {dev: [o.start for o in ops] for dev, ops in tr.ops.items()}
        for dev, prog, s, e, at in sorted(runs, key=lambda r: (r[0], r[2])):
            ops = tr.ops.get(dev, [])
            lo = bisect.bisect_left(starts.get(dev, []), s)
            hi = bisect.bisect_left(starts.get(dev, []), e)
            busy = base.covered([(o.start, o.end) for o in ops[lo:hi]], s, e)
            tr.launches.setdefault(dev, []).append(
                Launch(dev, prog, s, e, busy, at, owner.get(at)))
        return tr

    # -- per call ------------------------------------------------------------
    def _in_calls(self) -> List[List[Launch]]:
        """The launches made inside each call span, by call."""
        out: List[List[Launch]] = [[] for _ in self.calls]
        starts = [c.start for c in self.calls]
        for runs in self.launches.values():
            for r in runs:
                if r.launched_at is None:
                    continue
                i = bisect.bisect_right(starts, r.launched_at) - 1
                if i >= 0 and r.launched_at < self.calls[i].end:
                    out[i].append(r)
        return out

    def stage_device_s(self, names: Iterable[str]) -> List[float]:
        """Per call, the device-busy seconds of the runs launched inside the
        program spans ``names``, mean over chips."""
        names = tuple(names)
        chips = max(1, len(self.ops))
        return [sum(r.busy_ns for r in runs if r.span in names) / chips / 1e9
                for runs in self._in_calls()]

    def device_s_by_span(self) -> Dict[Optional[str], float]:
        """Device-busy seconds per call of the runs launched in each span
        (``None``: in no program span), mean over calls and chips."""
        total: Dict[Optional[str], int] = {}
        for runs in self._in_calls():
            for r in runs:
                total[r.span] = total.get(r.span, 0) + r.busy_ns
        per = max(1, len(self.ops)) * max(1, len(self.calls)) * 1e9
        return {k: v / per for k, v in sorted(total.items(), key=lambda kv: -kv[1])}

    def program_stat(self, stat: str) -> List[float]:
        """Per call, the sum of ``stat`` over the program spans that start
        inside it."""
        return [float(sum(sp.stats.get(stat, 0) for sp in self.program_spans
                          if c.start <= sp.start < c.end))
                for c in self.calls]

    # -- breakdown -------------------------------------------------------------
    def idle_gaps(self, k: int = 10) -> List[List]:
        """As :meth:`bench.trace.Trace.idle_gaps`, cut and named by the
        benchmark's spans, the program's and JAX's lowering and compile
        spans together."""
        every = self.spans + self.compile_spans + [
            base.Span(sp.name, sp.start, sp.end) for sp in self.program_spans]
        every.sort(key=lambda sp: sp.start)
        return base.Trace.idle_gaps(dataclasses.replace(self, spans=every), k)
