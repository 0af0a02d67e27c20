"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle time,
time per program and per operation, collectives and how much of them is
exposed, and idle gaps attributed to the benchmark's host spans.

Read with ``jax.profiler.ProfileData`` and nothing else. A TPU's plane
``/device:TPU:<n>`` carries one line of executed programs (``XLA Modules``),
one of the operations the core executed (``XLA Ops``) and one of transfers
in flight beside them (``Async XLA Ops``: from a ``-start`` to its ``-done``);
the host's planes carry the ``TraceAnnotation`` spans the harness opens
(prefix ``pb/``). An operation's event is named by its whole HLO line
(``%fusion.3 = bf16[..] fusion(..)``): it is kept as ``fusion.3`` with the
opcode ``fusion`` as its category. Times are nanoseconds. Operations nest
on their line (a ``while`` covers its body's operations), so sums are over
self time and busy time is the union of intervals, never a plain sum.

The device's clock and the host's are not the same clock: in the trace
recorded on a v5e for the tests the device's events lie 1-2 ms before the
host calls that caused them. Against steps of seconds that is nothing; a
gap shorter than a few milliseconds cannot be attributed to a host span.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

OPS_LINE = re.compile(r"^XLA Ops$")
ASYNC_LINE = re.compile(r"^Async XLA Ops$")
MODULES_LINE = re.compile(r"^XLA Modules$")
OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the device's clock runs 1-2 ms ahead of the host's in a trace: the window
#: opens this much before the host span that names it, so that the first
#: program of the window is inside (the harness dispatches nothing in the
#: tenths of a second before it opens the span)
CLOCK_SKEW_NS = 5e6
#: collectives by operation name; XLA:TPU wraps the asynchronous ones as
#: ``async-collective-start.N`` / ``async-collective-done.N`` fusions
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|async-collective")


class Event(NamedTuple):
    name: str
    start: float  # ns
    dur: float  # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]  # device plane -> operations, sorted by start
    modules: Dict[str, List[Event]]  # device plane -> programs
    in_flight: Dict[str, List[Event]]  # device plane -> async transfers
    host: List[Event]  # the harness's spans, prefix stripped
    window: Tuple[float, float]  # ns
    lines: Dict[str, Dict[str, int]]  # plane -> line -> events (inventory)
    categories: Dict[str, str]  # operation name -> its HLO opcode
    results: Dict[str, str]  # operation name -> its result type


def program_name(module_event_name: str) -> str:
    """``jit_generation(1234567)`` -> ``jit_generation``."""
    return re.sub(r"\(\d+\)$", "", module_event_name).strip()


def operation(event_name: str) -> Tuple[str, str, str]:
    """``%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop`` ->
    (``fusion.3``, ``fusion``, ``bf16[8]``): name, opcode and result type
    (layouts dropped); a name that is no HLO line stays whole."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name, "", ""
    found = OPCODE.search(" " + rest)
    if not found:
        return head.lstrip("%"), "", ""
    result = re.sub(r"\{[^{}]*\}", "", rest[:max(found.start() - 1, 0)])
    return head.lstrip("%"), found.group(1), result.strip()


def load(path, span_prefix: str = "pb/", window_span: Optional[str] = None,
         device_plane=DEVICE_PLANE, ops_line=OPS_LINE,
         modules_line=MODULES_LINE) -> Trace:
    """Reduce one ``.xplane.pb``. The three patterns say which planes are
    devices and which of their lines hold operations and programs; the
    defaults are a TPU's (the tests' CPU rehearsal passes the CPU client's)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    in_flight: Dict[str, List[Event]] = {}
    host: List[Event] = []
    lines: Dict[str, Dict[str, int]] = {}
    categories: Dict[str, str] = {}
    results: Dict[str, str] = {}
    short: Dict[str, str] = {}  # an event's whole name -> the operation's

    def operations(events) -> List[Event]:
        out = []
        for e in events:
            if e.duration_ns <= 0:
                continue
            if e.name not in short:
                short[e.name], opcode, result = operation(e.name)
                categories.setdefault(short[e.name], opcode)
                results.setdefault(short[e.name], result)
            out.append(Event(short[e.name], float(e.start_ns),
                             float(e.duration_ns)))
        return out

    for plane in data.planes:
        inventory = lines.setdefault(plane.name, {})
        is_device = bool(device_plane.match(plane.name))
        for line in plane.lines:
            events = list(line.events)
            inventory[line.name] = inventory.get(line.name, 0) + len(events)
            if is_device and ops_line.match(line.name):
                ops.setdefault(plane.name, []).extend(operations(events))
            elif is_device and ASYNC_LINE.match(line.name):
                in_flight.setdefault(plane.name, []).extend(operations(events))
            elif is_device and modules_line.match(line.name):
                modules.setdefault(plane.name, []).extend(
                    Event(e.name, float(e.start_ns), float(e.duration_ns))
                    for e in events if e.duration_ns > 0)
            # the host's planes; a CPU "device" shares its plane with them
            if not is_device or not DEVICE_PLANE.match(plane.name):
                host.extend(
                    Event(e.name[len(span_prefix):], float(e.start_ns),
                          float(e.duration_ns))
                    for e in events if e.name.startswith(span_prefix))
    for events in (list(ops.values()) + list(modules.values())
                   + list(in_flight.values())):
        events.sort(key=lambda e: (e.start, -e.dur))
    host.sort(key=lambda e: (e.start, -e.dur))
    window = None
    if window_span is not None:
        want = window_span[len(span_prefix):]
        for e in host:
            if e.name == want:
                window = (e.start - CLOCK_SKEW_NS, e.end)
    if window is None:
        every = [e for evs in list(ops.values()) + list(modules.values())
                 for e in evs]
        if not every:
            raise ValueError(
                f"{path}: no device operation in the trace; planes and "
                f"lines: {lines}")
        window = (min(e.start for e in every), max(e.end for e in every))
    return Trace(ops=ops, modules=modules, in_flight=in_flight, host=host,
                 window=window, lines=lines, categories=categories,
                 results=results)


# --------------------------------------------------------------------------- #
# intervals
# --------------------------------------------------------------------------- #


def merged(intervals: Iterable[Tuple[float, float]], lo: float,
           hi: float) -> List[Tuple[float, float]]:
    """Sorted, disjoint intervals covering the union, clipped to [lo, hi]."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(intervals, lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(intervals, lo, hi))


def subtract(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """``a`` minus ``b``, both sorted and disjoint."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _device_events(trace: Trace) -> Dict[str, List[Event]]:
    """Operations where the plane has them, else its programs."""
    return {p: trace.ops.get(p) or trace.modules.get(p, [])
            for p in set(trace.ops) | set(trace.modules)}


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which an operation ran, averaged over the
    devices in the trace."""
    per_device = [covered(((e.start, e.end) for e in evs), lo, hi)
                  for evs in _device_events(trace).values()]
    return sum(per_device) / len(per_device) / 1e9 if per_device else 0.0


def busy_seconds_within(trace: Trace, spans: List[Tuple[float, float]]
                        ) -> Tuple[float, float]:
    """(busy seconds, span seconds) summed over host spans, device-averaged."""
    busy = sum(busy_seconds(trace, a, b) for a, b in spans)
    return busy, sum(b - a for a, b in spans) / 1e9


# --------------------------------------------------------------------------- #
# operations and programs
# --------------------------------------------------------------------------- #


def self_times(events: List[Event]) -> List[Tuple[Event, float, bool]]:
    """(event, self time, is a leaf) for events of one line sorted by start:
    an event's self time is its duration less the events nested in it."""
    out: List[List] = []
    stack: List[int] = []
    for e in events:
        # nested means wholly inside; an event that outlasts the one before
        # it is its neighbour, not its child
        while stack and (out[stack[-1]][0].end <= e.start
                         or e.end > out[stack[-1]][0].end):
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= e.dur
            parent[2] = False
        out.append([e, e.dur, True])
        stack.append(len(out) - 1)
    return [(e, max(s, 0.0), leaf) for e, s, leaf in out]


def _in_window(events: List[Event], lo: float, hi: float) -> List[Event]:
    return [e for e in events if e.start >= lo and e.end <= hi]


def op_seconds(trace: Trace, lo: float, hi: float,
               keep: Optional[Callable[[str], bool]] = None,
               inside: Optional[Callable[[str], bool]] = None
               ) -> Dict[str, float]:
    """Self seconds per operation name, device-averaged. ``keep`` filters
    operation names; ``inside`` keeps operations that ran inside a program
    whose name it accepts."""
    total: Dict[str, float] = defaultdict(float)
    n = max(len(trace.ops), 1)
    for plane, events in trace.ops.items():
        spans = None
        if inside is not None:
            spans = merged(((m.start, m.end)
                            for m in trace.modules.get(plane, [])
                            if inside(program_name(m.name))), lo, hi)
            starts = [a for a, _ in spans]
        for e, self_ns, _ in self_times(_in_window(events, lo, hi)):
            if keep is not None and not keep(e.name):
                continue
            if spans is not None:
                i = bisect.bisect_right(starts, e.start) - 1
                if i < 0 or e.start >= spans[i][1]:
                    continue
            total[e.name] += self_ns / n / 1e9
    return dict(total)


def program_seconds(trace: Trace, lo: float, hi: float
                    ) -> Dict[str, Tuple[float, int]]:
    """program name -> (seconds, calls), device-averaged."""
    total: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0])
    n = max(len(trace.modules), 1)
    for events in trace.modules.values():
        for e in _in_window(events, lo, hi):
            slot = total[program_name(e.name)]
            slot[0] += e.dur / n / 1e9
            slot[1] += 1.0 / n
    return {k: (v[0], int(round(v[1]))) for k, v in total.items()}


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(name))


def collective_seconds(trace: Trace, lo: float, hi: float
                       ) -> Tuple[float, float]:
    """(seconds in which a collective was executing or in flight, seconds of
    them during which no other operation ran on that device), device-
    averaged. A collective counts from its ``-start`` to its ``-done`` (the
    ``Async XLA Ops`` line) and while the core executes either; "another
    operation" is a leaf that is no collective: a ``while`` that covers its
    body is not an operation that ran beside it."""
    total = exposed = 0.0
    n = max(len(trace.ops), 1)
    for plane, events in trace.ops.items():
        leaves = [e for e, _, leaf in self_times(_in_window(events, lo, hi))
                  if leaf]
        coll = merged(
            [(e.start, e.end) for e in leaves if is_collective(e.name)]
            + [(e.start, e.end) for e in trace.in_flight.get(plane, [])
               if is_collective(e.name)], lo, hi)
        rest = merged(((e.start, e.end) for e in leaves
                       if not is_collective(e.name)), lo, hi)
        total += sum(b - a for a, b in coll)
        exposed += sum(b - a for a, b in subtract(coll, rest))
    return total / n / 1e9, exposed / n / 1e9


# --------------------------------------------------------------------------- #
# idle gaps and the breakdown
# --------------------------------------------------------------------------- #


def idle_gaps(trace: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Idle seconds of the first device in [lo, hi], by the innermost host
    span that covers the middle of each gap."""
    devices = _device_events(trace)
    if not devices:
        return {}
    events = devices[sorted(devices)[0]]
    busy = merged(((e.start, e.end) for e in events), lo, hi)
    out: Dict[str, float] = defaultdict(float)
    for a, b in subtract([(lo, hi)], busy):
        mid = (a + b) / 2
        cover = [s for s in trace.host if s.start <= mid < s.end]
        name = min(cover, key=lambda s: s.dur).name if cover else "no span"
        out[name] += (b - a) / 1e9
    return dict(out)


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> Dict:
    """The operations that took most device time, each with its result type
    (an unnamed ``fusion.362`` says nothing, ``f32[8,152064]`` says which
    matmul it is), and the idle seconds by host span."""
    ops = sorted(op_seconds(trace, lo, hi).items(), key=lambda kv: -kv[1])
    gaps = sorted(idle_gaps(trace, lo, hi).items(), key=lambda kv: -kv[1])
    label = lambda k: f"{k} {trace.results.get(k, '')}".strip()[:96]  # noqa: E731
    return {"device_ops": [[label(k), v] for k, v in ops[:top]],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}
