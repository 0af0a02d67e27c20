"""The readers that this PR's cell adds, on traces small enough to work out
by hand: the scope helper on an ``.xplane.pb`` written here field by field
(and on the trace recorded on a v5e), the phase reader on a hand-built
``Trace``, the two count-based readers on hand-made records."""

import re
import types
from pathlib import Path

import pytest

from perfbench import counts_hybrid
from perfbench.layer_metrics import (_common, _scopes,
                                     decode_hbm_share_hybrid,
                                     learn_mfu_hybrid, ssm_scan_share,
                                     ssm_step_share, state_restore_ms)
from perfbench.tests.test_counts_hybrid import TINY
from perfbench.xplane import Event, Trace

RECORDED = Path(__file__).parent / "data" / "small_v5e.xplane.pb"


# -- a protobuf writer for the few messages an xplane file is made of -------
def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def metadata(key, name, tf_op="", program=0):
    body = field(1, key) + field(2, name)
    if tf_op:  # XStat: metadata_id, str_value
        body += field(5, field(1, 1) + field(5, tf_op))
    if program:  # XStat: metadata_id, uint64_value
        body += field(5, field(1, 2) + field(3, program))
    return field(4, field(1, key) + field(2, body))  # map entry of the plane


def line(name, events):
    body = field(2, name) + field(3, 1000)  # timestamp_ns
    for meta, offset_ps, dur_ps in events:
        body += field(4, field(1, meta) + field(2, offset_ps)
                      + field(3, dur_ps))
    return field(3, body)


def plane(name, metas, lines):
    stat_names = b"".join(
        field(5, field(1, key) + field(2, field(1, key) + field(2, text)))
        for key, text in ((1, "tf_op"), (2, "program_id")))
    return field(1, field(2, name) + b"".join(metas) + stat_names
                 + b"".join(lines))


US = 1_000_000  # picoseconds in a microsecond


def by_hand(tmp_path, scopes=True) -> Path:
    """One chip. Programs: ``jit_update`` (id 11) 0..100 us,
    ``jit_logprobs`` (12) 200..240 us, ``jit__decode_chunk_impl`` (13)
    300..400 us. Operations: in the update a ``while.1`` 10..60 under
    ``ssm/scan_bwd`` with its body's ``fusion.1`` 20..30 and 40..50 inside
    it (nested: the union is 50 us, not 70), and a matmul 70..90 under no
    scope; in the log-probability pass a ``fusion.2`` 205..215 under
    ``ssm/scan``; in the decode chunk a ``fusion.3`` 310..325 under
    ``ssm/step`` and a ``fusion.1`` 330..390 under none — the same NAME as
    the update's scoped ``fusion.1``: only the program tells them apart."""
    scope = (lambda s: s) if scopes else (lambda s: "elsewhere")
    metas = [
        metadata(1, "jit_update(11)"),
        metadata(2, "jit_logprobs(12)"),
        metadata(3, "jit__decode_chunk_impl(13)"),
        metadata(4, "%while.1 = (f32[8]) while(%t)",
                 scope("jit(update)/transpose/ssm/scan_bwd/while"), 11),
        metadata(5, "%fusion.1 = f32[8] fusion(%a)",
                 scope("jit(update)/transpose/ssm/scan_bwd/while/body/mul"), 11),
        metadata(6, "%convolution.1 = bf16[8] fusion(%a)",
                 "jit(update)/dot_general", 11),
        metadata(7, "%fusion.2 = f32[8] fusion(%a)",
                 scope("jit(logprobs)/ssm/scan/while/body/add"), 12),
        metadata(8, "%fusion.3 = f32[8] fusion(%a)",
                 scope("jit(_decode_chunk_impl)/while/body/ssm/step/mul"), 13),
        metadata(9, "%fusion.1 = f32[8] fusion(%b)",
                 "jit(_decode_chunk_impl)/while/body/dot_general", 13),
    ]
    modules = line("XLA Modules", [(1, 0, 100 * US), (2, 200 * US, 40 * US),
                                   (3, 300 * US, 100 * US)])
    ops = line("XLA Ops", [
        (4, 10 * US, 50 * US), (5, 20 * US, 10 * US), (5, 40 * US, 10 * US),
        (6, 70 * US, 20 * US), (7, 205 * US, 10 * US),
        (8, 310 * US, 15 * US), (9, 330 * US, 60 * US)])
    space = plane("/device:TPU:0", metas, [modules, ops]) \
        + plane("/host:CPU", [], [line("python", [])])
    path = (tmp_path / ".perfbench_trace" / "cell" / "plugins" / "profile"
            / "run" / "host.xplane.pb")
    path.parent.mkdir(parents=True)
    path.write_bytes(space)
    return path


def ctx_for(tmp_path, path=None):
    from perfbench import xplane

    cell = types.SimpleNamespace(root=tmp_path, name="cell", chips=1)
    trace = xplane.load(path) if path is not None else None
    return types.SimpleNamespace(cell=cell, trace=trace)


def test_metadata_is_keyed_by_program_and_operation(tmp_path):
    found = _scopes.operation_scopes(str(by_hand(tmp_path)))
    assert found[(11, "fusion.1")].endswith("ssm/scan_bwd/while/body/mul")
    assert found[(13, "fusion.1")].endswith("while/body/dot_general")
    assert (11, "jit_update(11)") not in found  # programs carry no tf_op


def test_seconds_under_a_scope_are_a_union_inside_the_named_programs(tmp_path):
    path = by_hand(tmp_path)
    ctx = ctx_for(tmp_path, path)
    scopes_of = _scopes.operation_scopes(str(path))
    read = lambda scopes, programs: _scopes.seconds(  # noqa: E731
        ctx.trace, scopes_of, scopes, programs)
    scoped, whole = read(("ssm/scan",), _common.LEARN_PROGRAMS)
    assert scoped == pytest.approx(60e-6) and whole == pytest.approx(140e-6)
    assert read(("ssm/scan/",), _common.LEARN_PROGRAMS)[0] == pytest.approx(10e-6)
    assert read(("ssm/scan_bwd",), _common.LEARN_PROGRAMS)[0] == pytest.approx(50e-6)
    assert read(("ssm/scan",), re.compile("no_such")) is None


def test_the_two_scope_shares(tmp_path):
    ctx = ctx_for(tmp_path, by_hand(tmp_path))
    assert ssm_scan_share.read(ctx) == pytest.approx(100 * 60 / 140)
    # the decode chunk's own fusion.1 is not the update's scoped fusion.1
    assert ssm_step_share.read(ctx) == pytest.approx(15.0)


def test_a_program_without_the_scopes_leaves_the_metrics_out(tmp_path):
    ctx = ctx_for(tmp_path, by_hand(tmp_path, scopes=False))
    assert ssm_scan_share.read(ctx) is None
    assert ssm_step_share.read(ctx) is None
    assert ssm_scan_share.read(ctx_for(tmp_path / "nothing")) is None


def test_the_decoder_reads_a_trace_recorded_on_the_chip():
    """``record_small.py``'s step is a 4-step scan of matmuls: everything
    but the copies in and out runs under ``while/body``."""
    from perfbench import xplane

    trace = xplane.load(RECORDED)
    scopes_of = _scopes.operation_scopes(str(RECORDED))
    assert all(program for program, _ in scopes_of)
    small = re.compile("small_step")
    scoped, whole = _scopes.seconds(trace, scopes_of, ("while/body",), small)
    assert 0.5 * whole < scoped <= whole
    assert _scopes.seconds(trace, scopes_of, ("ssm/scan",), small)[0] == 0.0


def test_state_restore_ms_is_the_mean_phase_per_hit():
    ms = 1e6
    span = lambda name, a, b: Event(name, a * ms, (b - a) * ms)  # noqa: E731
    host = [span("window", 0, 100), span("sched/admit", 10, 20),
            span("sched/state_restore", 11, 12),
            span("sched/state_restore", 13, 16),
            span("sched/state_restore", 200, 300)]  # outside the window
    trace = Trace(ops={}, modules={}, in_flight={}, host=host,
                  window=(0.0, 100 * ms), lines={}, categories={}, results={})
    ctx = types.SimpleNamespace(trace=trace)
    assert state_restore_ms.read(ctx) == pytest.approx(2.0)
    trace.host = host[:2]
    assert state_restore_ms.read(ctx) is None


def test_the_two_count_based_readers(monkeypatch):
    config = dict(TINY, serving={"slots": 3, "decode_chunk": 4},
                  agent={"lora_rank": 2, "lora_targets": ["wq", "in_proj"]})
    cell = types.SimpleNamespace(config=config, chips=1,
                                 traffic={"new_tokens": 8})
    records = [{"learn_s": 2.0, "row_lengths": [12, 14]},
               {"learn_s": 4.0, "row_lengths": [12, 14]}]
    peaks = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6}
    ctx = types.SimpleNamespace(cell=cell, records=records, peaks=peaks)
    flops = counts_hybrid.grpo_learn_flops(config, [12, 14], 2,
                                           ["wq", "in_proj"])
    assert learn_mfu_hybrid.read(ctx) == pytest.approx(
        100 * (flops / 2.0 / 1e6 + flops / 4.0 / 1e6) / 2)
    # 0.5 ms a decode step; live KV at the middle of the rollout: 8 + 10
    monkeypatch.setattr(decode_hbm_share_hybrid.decode_ms_per_step, "read",
                        lambda ctx: 0.5)
    least = counts_hybrid.decode_step_bytes(config, 18, 3)
    assert decode_hbm_share_hybrid.read(ctx) == pytest.approx(
        100 * (least / 1e6) / 0.5e-3)
