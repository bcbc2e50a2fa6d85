"""Reduce a profiler trace of the window to device busy time, per-call
device time, collective time and labelled idle gaps.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. As a v5e writes it (read by hand, JAX 0.9):

* each chip has a plane ``/device:TPU:<id>``; its line ``XLA Modules``
  holds one event per program run (``jit_scan(<fingerprint>)``), its line
  ``XLA Ops`` one event per HLO operation, named by the operation's HLO
  text (``%fusion.114 = u32[348160]{0} fusion(...), kind=kCustom``), and
  its line ``Async XLA Ops`` the spans of asynchronous operations from
  their start to their end, which overlap the others;
* the host plane ``/host:CPU`` has a line per thread; the benchmark's own
  spans (``bench.call`` around each call, ``bench.dispatch`` around issuing
  it, ``bench.sync`` around waiting for it) are on the Python thread's.

All events share one clock, in ns. Busy time is the union of the ``XLA
Ops`` events; the asynchronous spans count only as collective time.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
# The benchmark's host spans: a whole call, issuing it, waiting for it.
SPAN_CALL, SPAN_DISPATCH, SPAN_SYNC = "bench.call", "bench.dispatch", "bench.sync"
SPAN_PREFIX = "bench."
# HLO opcodes of the operations that move data between chips; the async
# forms (``all-gather-start`` / ``-done``) share the prefix.
COLLECTIVE_OPCODES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
                      "collective-permute", "collective-broadcast",
                      "ragged-all-to-all")
# HLO opcodes whose span holds the ops of the computations they call.
CONTROL_FLOW_OPCODES = ("while", "conditional", "call")
HLO_NAME = re.compile(r"%?([\w.\-]+)\s*=\s*")
HLO_OPCODE = re.compile(r"\s*([a-z][a-z0-9\-]*)\(")

Interval = Tuple[int, int]


@dataclasses.dataclass(frozen=True)
class Op:
    name: str            # "<program>/<instruction>", as "jit_scan/while.3"
    start: int
    end: int
    opcode: str          # the HLO opcode: "while", "fusion", "all-to-all", ...


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _strip_suffix(name: str) -> str:
    return re.sub(r"(\.\d+)+$", "", name)


def parse_hlo(text: str) -> Tuple[str, str]:
    """``(instruction name, opcode)`` of one line of HLO text,
    ``%name = <type> opcode(operands), attributes``; a type that is a tuple
    is skipped by its brackets. Text that is no HLO line is taken for a bare
    instruction name, which HLO makes from the opcode and a ``.N`` suffix."""
    m = HLO_NAME.match(text)
    if not m:
        return text, _strip_suffix(text)
    name, rest = m.group(1), text[m.end():]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    op = HLO_OPCODE.match(rest)
    return name, op.group(1) if op else _strip_suffix(name)


def hlo_text(name: str, stats: Dict[str, object]) -> str:
    """The HLO text of a device op: its event name, or its ``long_name``
    where the name is only the instruction's."""
    text = stats.get("long_name")
    return text if isinstance(text, str) and " = " not in name else name


def program_name(event_name: str) -> str:
    """``jit_scan(9095189347656157107)`` -> ``jit_scan``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def is_collective(opcode: str) -> bool:
    return any(opcode == c or opcode.startswith(c + "-") for c in COLLECTIVE_OPCODES)


def union(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """The union of ``intervals`` clipped to ``[lo, hi)``, sorted, disjoint."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def covered(intervals: Sequence[Interval], lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The stretches of ``[lo, hi)`` that ``busy`` (disjoint, sorted) leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _in_programs(events, programs) -> List[Op]:
    """Device ops named ``<program>/<instruction>`` after the program run
    that holds each; both lists sorted by start."""
    out, j = [], 0
    for name, start, end, opcode in events:
        while j < len(programs) and programs[j][2] <= start:
            j += 1
        prog = programs[j][0] if j < len(programs) and programs[j][1] <= start else "?"
        out.append(Op(f"{prog}/{name}", start, end, opcode))
    return out


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Op]]      # device id -> its ops, by start
    async_ops: Dict[int, List[Op]]  # device id -> its asynchronous spans
    spans: List[Span]             # the benchmark's host spans, by start
    calls: List[Span]             # the spans of whole calls

    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        return cls.from_profile(ProfileData.from_file(path))

    @classmethod
    def from_profile(cls, data) -> "Trace":
        """From a ``jax.profiler.ProfileData``."""
        ops: Dict[int, List[Op]] = {}
        async_ops: Dict[int, List[Op]] = {}
        spans: List[Span] = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                lines = {line.name: list(line.events) for line in plane.lines}
                programs = sorted(((program_name(ev.name), int(ev.start_ns),
                                    int(ev.end_ns)) for ev in lines.get(MODULES_LINE, ())),
                                  key=lambda p: p[1])
                dev = int(m.group(1))
                for line, into in ((OPS_LINE, ops), (ASYNC_LINE, async_ops)):
                    events = []
                    for ev in lines.get(line, ()):
                        name, opcode = parse_hlo(hlo_text(ev.name, dict(ev.stats)))
                        events.append((name, int(ev.start_ns), int(ev.end_ns), opcode))
                    events.sort(key=lambda e: e[1])
                    into[dev] = _in_programs(events, programs)
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    spans.extend(Span(ev.name, int(ev.start_ns), int(ev.end_ns))
                                 for ev in line.events
                                 if ev.name.startswith(SPAN_PREFIX))
        spans.sort(key=lambda s: s.start)
        return cls(ops, async_ops, spans, [s for s in spans if s.name == SPAN_CALL])

    # -- the window --------------------------------------------------------
    def window(self) -> Interval:
        """From the first call's start to the last call's end."""
        if not self.calls:
            raise ValueError("the trace holds no call spans")
        return self.calls[0].start, max(c.end for c in self.calls)

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    def busy(self, device: int, lo: int, hi: int) -> List[Interval]:
        return union(((o.start, o.end) for o in self.ops[device]), lo, hi)

    def busy_s(self) -> float:
        """Seconds in which an op ran, within the window, mean over chips."""
        lo, hi = self.window()
        if not self.ops:
            return 0.0
        return sum(covered([(o.start, o.end) for o in ops], lo, hi)
                   for ops in self.ops.values()) / len(self.ops) / 1e9

    # -- per call ------------------------------------------------------------
    def call_busy_s(self) -> List[float]:
        """Device-busy seconds inside each call span, mean over chips."""
        out = []
        for c in self.calls:
            out.append(sum(covered([(o.start, o.end) for o in ops], c.start, c.end)
                           for ops in self.ops.values()) / max(1, len(self.ops)) / 1e9)
        return out

    def collective_s_per_call(self) -> Optional[float]:
        """Device seconds of collective ops per call on the slowest chip, or
        ``None`` where no collective ran."""
        lo, hi = self.window()
        worst, seen = 0.0, False
        for dev, ops in self.ops.items():
            coll = [(o.start, o.end) for o in ops + self.async_ops.get(dev, [])
                    if is_collective(o.opcode)]
            seen = seen or bool(coll)
            worst = max(worst, covered(coll, lo, hi) / 1e9)
        return worst / len(self.calls) if seen else None

    # -- breakdown -------------------------------------------------------------
    def top_ops(self, k: int = 10) -> List[List]:
        """The ops that took the most device time per call, by name, mean
        over chips: ``[[name, seconds per call], ...]``. A ``while`` or
        other control-flow op is left out: the trace also holds the ops of
        its body, which run inside its span."""
        lo, hi = self.window()
        total: Dict[str, float] = collections.Counter()
        for ops in self.ops.values():
            for o in ops:
                if o.opcode in CONTROL_FLOW_OPCODES:
                    continue
                s, e = max(o.start, lo), min(o.end, hi)
                if e > s:
                    total[o.name] += (e - s) / 1e9
        per = len(self.ops) * len(self.calls)
        return [[name, secs / per] for name, secs in total.most_common(k)]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The longest stretches of the window in which the first chip ran
        nothing, cut where a benchmark span opens or closes, each named by
        the innermost span open then (``bench.dispatch``, ``bench.sync``)
        or ``between calls``: ``[[label, seconds], ...]``."""
        lo, hi = self.window()
        busy = self.busy(min(self.ops), lo, hi) if self.ops else []
        edges = sorted({t for sp in self.spans for t in (sp.start, sp.end)})
        pieces = []
        for s, e in gaps(busy, lo, hi):
            cuts = [s] + [t for t in edges if s < t < e] + [e]
            pieces.extend(zip(cuts, cuts[1:]))
        out = []
        for s, e in sorted(pieces, key=lambda g: g[0] - g[1])[:k]:
            inner = [sp for sp in self.spans if sp.start <= s and e <= sp.end]
            label = (min(inner, key=lambda sp: sp.end - sp.start).name if inner
                     else "between calls")
            out.append([label, (e - s) / 1e9])
        return out

    def breakdown(self) -> Dict[str, List[List]]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}
