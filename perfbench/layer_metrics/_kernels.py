"""What a device program names inside itself, read from a cell's own
``.xplane.pb``: the milliseconds under a ``device_scope`` of the decode chunk
or the generation program, and a named Pallas kernel against the chip's
bf16 peak.

A scope is found as ``_scopes`` finds it (the ``tf_op`` of an operation's
metadata). A kernel is found by the stem of its ``pallas_call``'s ``name=``
anywhere in the operation's name: jax decorates the name with the transforms
above the call (``jvp_fused_loss_fwd_.1``, ``transpose_jvp_fused_loss_dh__.1``).
A kernel's FLOPs are counted **per execution, from the event's own result
type and the cell's configuration**, and summed over the executions found
inside the learn programs — never from a count of passes: an execution more
or fewer, or one at another N, changes FLOPs and seconds together. The result
type is the metadata's, keyed by (program id, operation name) like the
scopes: ``fused_loss_fwd.1`` is one kernel in one program only. On a mesh the
shapes are a chip's own and FLOPs and seconds are both summed over the
chips, so nothing is divided by their number.

FLOPs a kernel is credited with (2 a multiply-add; causal attention is half
of the Tp x Tp square, and what the kernels compute beyond it — the upper
half of every diagonal block, ``1 + block / Tp`` of the count — is the
kernel's cost, not work the algorithm asks for):

- ``fused_loss_fwd`` (result ``(f32[N,1], f32[N,1])``): 2 N D V.
- ``fused_loss_dh`` (result ``[N,D]``): 4 N D V — it forms the logits again
  (2 N D V) and multiplies their gradient by the head (2 N D V).
- ``flash_fwd`` (result ``(bf16[BH,Tp,dv], f32[BH,Tp,1])``): QK^T and PV,
  BH Tp^2 (d + dv).
- ``flash_dq`` (result ``[BH,Tp,d]``): QK^T, dO V^T and dS K,
  BH Tp^2 (2 d + dv).
- ``flash_dkv`` (result ``([BH,Tp,d], [BH,Tp,dv])``): QK^T, P^T dO, dO V^T
  and dS^T Q, BH Tp^2 (2 d + 2 dv). With ``flash_dq``: BH Tp^2 (4 d + 3 dv).

N, BH and Tp come from the event; D, V and the two head widths from the
configuration's published keys (q and k ``qk_head_dim`` wide and v
``v_head_dim`` where the file has them: 192 and 128 under latent attention).

    python3 -m perfbench.layer_metrics._kernels <file.xplane.pb> [workload]

prints, per program, the device seconds under every top-level scope and, per
named kernel, executions, seconds and TFLOP/s (the workload, whose
configuration the FLOPs need, is read from a path under
``.perfbench_trace/<workload>/`` where it is not given).
"""

from __future__ import annotations

import bisect
import functools
import re
from collections import defaultdict
from pathlib import Path
from typing import Any, Callable, Dict, Iterator, List, NamedTuple, Optional, Tuple

from perfbench import xplane
from perfbench.layer_metrics import _common, _scopes

ARRAY = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")
#: components of a ``tf_op`` that jax makes itself; what is left before the
#: primitive's own name are the scopes the program put on
JAX_MADE = re.compile(
    r"^(while|body|cond|closed_call|checkpoint|remat\d*|rematted_computation"
    r"|core_call|custom_jvp_call|custom_vjp_call|custom_vjp_call_jaxpr"
    r"|custom_lin|shard_map|pjit|branch_\d+_fun)$")
PLAIN = re.compile(r"^[a-z][a-z0-9_]*$")
TRANSFORMED = re.compile(r"\b(?:vmap|jvp|transpose|pmap)\(([^()]*)\)")


class Operation(NamedTuple):
    result: str  # the HLO result type, layouts dropped
    tf_op: str  # the instruction's op_name: jit(f)/.../scope/primitive


def shapes(result: str) -> List[Tuple[int, ...]]:
    """``(bf16[64,1024,128], f32[64,1024,1])`` -> [(64, 1024, 128),
    (64, 1024, 1)]."""
    return [tuple(int(n) for n in dims.split(",") if n)
            for dims in ARRAY.findall(result)]


def head_widths(config: Dict[str, Any]) -> Tuple[int, int]:
    """(width of a query or key head, width of a value head)."""
    d = int(config.get("qk_head_dim") or config.get("head_dim")
            or config["hidden_size"] // config["num_attention_heads"])
    return d, int(config.get("v_head_dim") or d)


def _fused_loss(passes: int) -> Callable:
    def flops(result: str, config: Dict[str, Any]) -> float:
        n = shapes(result)[0][0]
        return (2.0 * passes * n * int(config["hidden_size"])
                * int(config["vocab_size"]))
    return flops


def _flash(qk: int, v: int) -> Callable:
    def flops(result: str, config: Dict[str, Any]) -> float:
        bh, tp = shapes(result)[0][:2]
        d, dv = head_widths(config)
        return float(bh) * tp * tp * (qk * d + v * dv)
    return flops


#: a kernel's stem -> FLOPs of one execution (its result type, the
#: configuration); the counts of the module's docstring
KERNEL_FLOPS: Dict[str, Callable[[str, Dict[str, Any]], float]] = {
    "fused_loss_fwd": _fused_loss(1),
    "fused_loss_dh": _fused_loss(2),
    "flash_fwd": _flash(1, 1),
    "flash_dq": _flash(2, 1),
    "flash_dkv": _flash(2, 2),
}


@functools.lru_cache(maxsize=2)
def operations(path: str, device_plane: str = r"^/device:TPU:\d+$"
               ) -> Dict[Tuple[int, str], Operation]:
    """(program id, operation name as ``xplane.Trace`` has it) -> the
    operation's result type and ``tf_op``, for every operation of the device
    planes (``_scopes.operation_scopes`` keeps the ``tf_op`` alone and drops
    an operation without one)."""
    plane_name = re.compile(device_plane)
    out: Dict[Tuple[int, str], Operation] = {}
    for number, plane in _scopes.fields(Path(path).read_bytes()):
        if number != 1:
            continue
        name, metadata, stat_names = "", [], {}
        for n, v in _scopes.fields(plane):
            if n == 2:
                name = _scopes._text(v)
            elif n == 4:
                metadata.append(_scopes._map_entry(v)[1])
            elif n == 5:
                key, value = _scopes._map_entry(v)
                for sn, sv in _scopes.fields(value):
                    if sn == 2:
                        stat_names[key] = _scopes._text(sv)
        if not plane_name.match(name):
            continue
        for meta in metadata:
            hlo, tf_op, program = "", "", 0
            for n, v in _scopes.fields(meta):
                if n == 2:
                    hlo = _scopes._text(v)
                elif n == 5:  # XStat
                    stat = dict(_scopes.fields(v))
                    which = stat_names.get(stat.get(1))
                    if which == "tf_op":
                        tf_op = (_scopes._text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
                    elif which == "program_id":
                        program = stat.get(3, stat.get(4, 0))
            short, opcode, result = xplane.operation(hlo)
            if opcode:
                out[(program, short)] = Operation(result, tf_op)
    return out


def program_runs(trace: xplane.Trace, programs: re.Pattern
                 ) -> Iterator[Tuple[str, str, int, List[xplane.Event]]]:
    """(chip's plane, program name, program id, the operations that started
    inside one execution of it) for every execution, inside the traced
    window, of a program whose name matches."""
    lo, hi = trace.window
    for plane, modules in trace.modules.items():
        ops = trace.ops.get(plane, [])
        starts = [e.start for e in ops]
        for m in modules:
            found = _scopes.PROGRAM_ID.search(m.name)
            name = xplane.program_name(m.name)
            if (found and m.start >= lo and m.end <= hi
                    and programs.search(name)):
                yield plane, name, int(found.group(1)), ops[
                    bisect.bisect_left(starts, m.start):
                    bisect.bisect_right(starts, m.end)]


def executions(trace: xplane.Trace, table: Dict[Tuple[int, str], Operation],
               stems, programs: re.Pattern
               ) -> List[Tuple[str, str, float]]:
    """(stem, result type, seconds) of every execution of a kernel whose
    operation name holds one of ``stems``, inside the matching programs, on
    every chip."""
    out = []
    for _, _, program, ops in program_runs(trace, programs):
        for e in ops:
            stem = next((s for s in stems if s in e.name), None)
            if stem is not None and (program, e.name) in table:
                out.append((stem, table[(program, e.name)].result,
                            e.dur / 1e9))
    return out


def roofline(ctx, stems) -> Optional[float]:
    """Percent of the bf16 peak that the kernels named by ``stems`` reached
    over their executions inside the learn programs; None without a trace
    file or such a kernel (a program from before it had the name)."""
    path = _scopes.cell_trace(ctx)
    if path is None:
        return None
    found = executions(ctx.trace, operations(str(path)), stems,
                       _common.LEARN_PROGRAMS)
    seconds = sum(s for _, _, s in found)
    if not seconds:
        return None
    flops = sum(KERNEL_FLOPS[stem](result, ctx.cell.config)
                for stem, result, _ in found)
    return 100.0 * flops / seconds / ctx.peaks["bf16_flops_per_s"]


def scope_ms(ctx, scope: str, programs: re.Pattern,
             steps_a_call: int = 1) -> Optional[float]:
    """Device milliseconds under ``scope`` a call of the matching program
    (a step of it where a call runs ``steps_a_call`` of them); None without
    a trace file, such a program or such a scope."""
    path = _scopes.cell_trace(ctx)
    if path is None:
        return None
    found = _scopes.seconds(ctx.trace, _scopes.operation_scopes(str(path)),
                            (scope,), programs)
    _, calls = _common.program_total(ctx, programs)
    if not found or not found[0] or not calls or not steps_a_call:
        return None
    return 1e3 * found[0] / (calls * steps_a_call)


def decode_scope_ms(ctx, scope: str) -> Optional[float]:
    """``scope_ms`` a decode step, as ``decode_ms_per_step`` divides."""
    chunk = ctx.cell.config.get("serving", {}).get("decode_chunk")
    return scope_ms(ctx, scope, _common.DECODE_PROGRAM, int(chunk or 0))


# --------------------------------------------------------------------------- #
# the command: every scope and every named kernel of a trace
# --------------------------------------------------------------------------- #


def top_scope(tf_op: str) -> str:
    """The outermost scope the program put on: the first two plain
    components of the ``tf_op`` that jax did not make, the primitive's name
    at its end left out (``jit(f)/while/body/decode/ffn/moe/experts/mul:``
    -> ``decode/ffn``); "" where there is none."""
    path, n = tf_op.rstrip(":"), 1
    while n:  # a transformed block's scope reads vmap(evo/rollout)
        path, n = TRANSFORMED.subn(r"\1", path)
    parts = path.split("/")[:-1]
    plain = [bool(PLAIN.match(p)) and not JAX_MADE.match(p) for p in parts]
    for i in range(len(parts) - 1):
        if plain[i] and plain[i + 1]:
            return f"{parts[i]}/{parts[i + 1]}"
    return ""


def summary(trace: xplane.Trace, table: Dict[Tuple[int, str], Operation],
            config: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """program -> {calls, seconds, scopes: {scope: seconds}, kernels:
    {stem: {executions, seconds, tflops}}}: calls, executions and seconds
    summed over the window and averaged over the chips. Time under a scope
    is a union of intervals (a ``while`` covers its body)."""
    lo, hi = trace.window
    chips = max(len(trace.modules), 1)
    out: Dict[str, Any] = {
        name: {"calls": calls, "seconds": seconds, "scopes": {},
               "kernels": {}}
        for name, (seconds, calls)
        in xplane.program_seconds(trace, lo, hi).items()}
    spans: Dict[Tuple[str, str, str], List[Tuple[float, float]]] = \
        defaultdict(list)
    for plane, name, program, ops in program_runs(trace, re.compile("")):
        kernels = out[name]["kernels"]
        for e in ops:
            found = table.get((program, e.name))
            if found is None:
                continue
            scope = top_scope(found.tf_op)
            if scope:
                spans[(name, scope, plane)].append((e.start, e.end))
            stem = next((s for s in KERNEL_FLOPS if s in e.name), None)
            if stem is not None:
                k = kernels.setdefault(
                    stem, {"executions": 0.0, "seconds": 0.0, "flops": 0.0})
                k["executions"] += 1.0 / chips
                k["seconds"] += e.dur / 1e9 / chips
                if config is not None:
                    k["flops"] += (KERNEL_FLOPS[stem](found.result, config)
                                   / chips)
    for (name, scope, _), intervals in sorted(spans.items()):
        scopes = out[name]["scopes"]
        scopes[scope] = (scopes.get(scope, 0.0)
                         + xplane.covered(intervals, lo, hi) / chips / 1e9)
    for entry in out.values():
        for k in entry["kernels"].values():
            flops = k.pop("flops")
            k["tflops"] = flops / k["seconds"] / 1e12 if flops else None
    return out


def main(argv: List[str]) -> None:
    import json

    from perfbench import harness

    path = Path(argv[1]).resolve()
    workload = argv[2] if len(argv) > 2 else next(
        (path.parts[i + 1] for i, p in enumerate(path.parts[:-1])
         if p == ".perfbench_trace"), None)
    config = None
    if workload is not None:
        root = Path(__file__).resolve().parents[2]
        config = harness.load_cell(root, workload).config
    trace = xplane.load(path, window_span="pb/window")
    print(json.dumps(summary(trace, operations(str(path)), config), indent=1))


if __name__ == "__main__":
    import sys

    main(sys.argv)
