"""``ssm_scan_roofline``, the reader that came with the selective scan's
Pallas kernels, on a trace small enough to work out by hand (the writer of
``test_scope_readers.py`` / ``test_scope_readers_device.py``, which are not
edited): the bytes of each execution from its own first result, the share
at the chip's bandwidth never above 100 % for a time at or over the bytes'
own, and ``None`` on a trace without the kernels (a program that lacks them,
or one whose gate sent the scan to the XLA form)."""

import math

import pytest

from perfbench.layer_metrics import ssm_scan_roofline as R
from perfbench.tests.test_scope_readers import US, metadata
from perfbench.tests.test_scope_readers_device import ctx_for, write

N = 16
CONFIG = {"mamba_d_state": N}
HBM = 819e9  # bytes/s: the v5e of peaks.json
FWD = "(bf16[8,1024,512]{2,1,0}, f32[8,16,512]{2,1,0}) custom-call(%x)"
FWD_STARTS = ("(bf16[8,1024,512]{2,1,0}, f32[8,16,512]{2,1,0}, "
              "f32[8,8,16,512]{3,2,1,0}) custom-call(%x)")
BWD = ("(bf16[8,1024,512]{2,1,0}, f32[8,1024,512]{2,1,0}, "
       "f32[8,1,32,1024]{3,2,1,0}, f32[8,16,512]{2,1,0}, "
       "f32[8,16,512]{2,1,0}) custom-call(%x)")
#: what each of them must move: 8 x 1024 positions over 512 lanes
FWD_BYTES = 8 * 1024 * (512 * (2 * 2 + 4) + 2 * N * 4)
BWD_BYTES = 8 * 1024 * (512 * (3 * 2 + 8) + 4 * N * 4)


def scan_trace(tmp_path, named=True, us=(10, 12, 30)):
    """``jit_logprobs`` (41) once: the forward ``ssm_scan_fwd.1``.
    ``jit_update`` (42): the forward again (with the block starts,
    ``jvp_ssm_scan_fwd_.1``) and the backward ``ssm_scan_bwd.1``. A prefill
    (43) runs a forward of its own, nobody's here. ``us`` the three learn
    executions' microseconds."""
    name = (lambda s: s) if named else (lambda s: "fusion")
    metas = [
        metadata(1, "jit_logprobs(41)"), metadata(2, "jit_update(42)"),
        metadata(3, "jit__prefill_impl(43)"),
        metadata(4, f"%{name('ssm_scan_fwd')}.1 = " + FWD,
                 "jit(logprobs)/ssm/scan/ssm_scan_fwd/pallas_call", 41),
        metadata(5, f"%jvp_{name('ssm_scan_fwd')}_.1 = " + FWD_STARTS,
                 "jit(update)/jvp(ssm/scan)/ssm_scan_fwd/pallas_call", 42),
        metadata(6, f"%{name('ssm_scan_bwd')}.1 = " + BWD,
                 "jit(update)/transpose(jvp(ssm/scan_bwd))/ssm_scan_bwd/"
                 "pallas_call", 42),
        metadata(7, f"%{name('ssm_scan_fwd')}.1 = " + FWD,
                 "jit(_prefill_impl)/ssm/scan/ssm_scan_fwd/pallas_call", 43),
    ]
    ps = [math.ceil(u * US) for u in us]  # never shorter than asked
    end = 10 * US + sum(ps) + 10 * US
    modules = [(1, 0, end), (2, end, end), (3, 2 * end, 100 * US)]
    ops = [(4, 5 * US, ps[0]), (5, end + 5 * US, ps[1]),
           (6, end + 5 * US + ps[1], ps[2]), (7, 2 * end + 5 * US, 50 * US)]
    return write(tmp_path, metas, modules, ops)


def ctx(tmp_path, **kw):
    c = ctx_for(tmp_path, scan_trace(tmp_path, **kw), CONFIG)
    c.peaks = {"hbm_bytes_per_s": HBM}
    return c


def test_the_bytes_of_each_execution_come_from_its_first_result():
    assert R.execution_bytes("ssm_scan_fwd", "(bf16[8,1024,512], "
                             "f32[8,16,512])", N) == FWD_BYTES
    assert R.execution_bytes("ssm_scan_bwd", "(bf16[8,1024,512], "
                             "f32[8,1024,512])", N) == BWD_BYTES
    # a float32 x: a = 4; half the lanes, half the bytes of D
    assert R.execution_bytes("ssm_scan_fwd", "(f32[2,128,256])", N) == \
        2 * 128 * (256 * 12 + 128)


def test_the_learn_programs_executions_over_their_seconds(tmp_path):
    # the no-grad forward, the update's forward and the backward; the
    # prefill's forward is not a learn program's
    want = (2 * FWD_BYTES + BWD_BYTES) / 52e-6 / HBM
    assert R.read(ctx(tmp_path)) == pytest.approx(100 * want)


@pytest.mark.parametrize("slower", [1.0, 1.5, 4.0, 40.0])
def test_at_or_over_the_bytes_own_time_the_share_stays_under_100(
        tmp_path, slower):
    # each execution as long as its bytes take at the chip's bandwidth (up
    # to a nanosecond, the trace's grain) or longer
    own = [math.ceil(b / HBM * 1e9) / 1e3 * slower
           for b in (FWD_BYTES, FWD_BYTES, BWD_BYTES)]
    share = R.read(ctx(tmp_path, us=tuple(own)))
    assert share <= 100.0
    assert share == pytest.approx(100.0 / slower, rel=1e-3)


def test_a_program_without_the_kernels_leaves_the_metric_out(tmp_path):
    assert R.read(ctx(tmp_path, named=False)) is None
