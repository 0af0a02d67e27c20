"""Device time under a ``jax.named_scope``, from a cell's own ``.xplane.pb``.

``xplane.Trace`` keeps an operation's name, opcode and result type. The
scope an operation was traced under (``ssm/scan``, ...) is in none of them:
it is in the ``tf_op`` stat of the event's METADATA (the HLO instruction's
``op_name``, e.g. ``jit(update)/.../ssm/scan/while/body/mul``), which
``jax.profiler.ProfileData`` does not hand out. So this file decodes the
protobuf's wire format itself, as far as it needs: a device plane's event
metadata with two of its stats, ``tf_op`` and ``program_id``. It never walks
the events (millions in a traced rollout): those it takes from the
``xplane.Trace`` the harness has loaded already, where an operation is
``fusion.12`` — a name that means something inside ONE program, hence the
``program_id``, which a program's event on ``XLA Modules`` carries in its
name (``jit_update(1234)``). Nothing is imported but the standard library
and ``perfbench.xplane``.

Operations nest on their line (a ``while`` covers its body), so time under
a scope is the union of the matching operations' intervals, never their
sum. A fusion's ``op_name`` is its root's; an operation that XLA fused
across a scope's edge counts on the side of the root. A trace with no such
scope (the parent of the PR that added it) gives the reader ``None``.
"""

from __future__ import annotations

import bisect
import functools
import re
from pathlib import Path
from typing import Dict, Iterator, Optional, Set, Tuple

from perfbench import xplane

PROGRAM_ID = re.compile(r"\((\d+)\)$")


def _varint(buf, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[at]
        at += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, at
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message; a length-delimited value is a
    view of its bytes, never a copy."""
    buf = memoryview(buf)
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 1:
            value, at = buf[at:at + 8], at + 8
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == 5:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key, value = 0, b""
    for number, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


@functools.lru_cache(maxsize=2)
def operation_scopes(path: str, device_plane: str = r"^/device:TPU:\d+$"
                     ) -> Dict[Tuple[int, str], str]:
    """(program id, operation name as ``xplane.Trace`` has it) -> the
    operation's ``tf_op``, for every operation of the device planes."""
    plane_name = re.compile(device_plane)
    out: Dict[Tuple[int, str], str] = {}
    for number, plane in fields(Path(path).read_bytes()):
        if number != 1:
            continue
        name, metadata, stat_names = "", [], {}
        for n, v in fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 4:
                metadata.append(_map_entry(v)[1])
            elif n == 5:
                key, value = _map_entry(v)
                for sn, sv in fields(value):
                    if sn == 2:
                        stat_names[key] = _text(sv)
        if not plane_name.match(name):
            continue
        for meta in metadata:
            hlo, tf_op, program = "", "", 0
            for n, v in fields(meta):
                if n == 2:
                    hlo = _text(v)
                elif n == 5:  # XStat
                    stat = dict(fields(v))
                    which = stat_names.get(stat.get(1))
                    if which == "tf_op":
                        tf_op = (_text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
                    elif which == "program_id":
                        program = stat.get(3, stat.get(4, 0))
            if tf_op:
                out[(program, xplane.operation(hlo)[0])] = tf_op
    return out


def seconds(trace: xplane.Trace, scopes_of: Dict[Tuple[int, str], str],
            scopes, programs: re.Pattern) -> Optional[Tuple[float, float]]:
    """(device seconds under any of ``scopes``, device seconds of the
    programs whose name matches) inside the traced window, averaged over
    the chips; None where no such program ran."""
    lo, hi = trace.window
    scoped = whole = 0.0
    chips = 0
    marked: Set[Tuple[int, str]] = {
        key for key, tf_op in scopes_of.items()
        if any(scope in tf_op for scope in scopes)}
    for plane, modules in trace.modules.items():
        runs = []
        for m in modules:
            found = PROGRAM_ID.search(m.name)
            if (found and m.start >= lo and m.end <= hi
                    and programs.search(xplane.program_name(m.name))):
                runs.append((m.start, m.end, int(found.group(1))))
        if not runs:
            continue
        chips += 1
        whole += xplane.covered(((a, b) for a, b, _ in runs), lo, hi)
        ops = trace.ops.get(plane, [])
        starts = [e.start for e in ops]
        inside = []
        for a, b, program in runs:
            for e in ops[bisect.bisect_left(starts, a):
                         bisect.bisect_right(starts, b)]:
                if (program, e.name) in marked:
                    inside.append((e.start, min(e.end, b)))
        scoped += xplane.covered(inside, lo, hi)
    if not chips:
        return None
    return scoped / chips / 1e9, whole / chips / 1e9


def cell_trace(ctx) -> Optional[Path]:
    """The newest ``.xplane.pb`` of the cell's traced run, where the harness
    leaves it."""
    root = ctx.cell.root / ".perfbench_trace" / ctx.cell.name
    files = sorted(root.glob("plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


def share(ctx, scopes, programs: re.Pattern) -> Optional[float]:
    """Percent of the matching programs' device time spent under the
    scopes; None without a trace file, such programs or such a scope."""
    path = cell_trace(ctx)
    if path is None:
        return None
    found = seconds(ctx.trace, operation_scopes(str(path)), scopes, programs)
    if not found or not found[0] or not found[1]:
        return None
    return 100.0 * found[0] / found[1]
