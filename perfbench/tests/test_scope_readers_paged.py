"""``paged_attend_share`` on a trace small enough to work out by hand (the
writer of ``test_scope_readers.py``, which came with the hybrid cell and is
not edited): the scope ``paged/attend`` inside the decode chunk, under
latent attention's own scope too, and a parent's program without it."""

import pytest

from perfbench.layer_metrics import paged_attend_share
from perfbench.tests.test_scope_readers import (US, ctx_for, line, metadata,
                                                plane)


def by_hand(tmp_path, scope="paged/attend"):
    """One chip, two calls of ``jit__decode_chunk_impl`` (id 7), 0..100 and
    200..300 us, and a ``jit_update`` (8) 400..500. In each decode call the
    layer loop's ``while.2`` (the attention loop: 10..40) with its body's
    gather ``fusion.5`` 12..20 and matmul ``convolution.3`` 22..38 nested
    inside — the union is 30 us a call, not 54 —, the same loop under
    latent attention's scope ``while.4`` 50..60, and the head ``fusion.9``
    60..95 under no scope. The update has a ``fusion.5`` of its own."""
    chunk = "jit(_decode_chunk_impl)/while/body/closed_call/"
    paged = chunk + f"jit(chunked_paged_attention)/{scope}/while"
    metas = [
        metadata(1, "jit__decode_chunk_impl(7)"),
        metadata(2, "jit_update(8)"),
        metadata(3, "%while.2 = (f32[8]) while(%t)", paged, 7),
        metadata(4, "%fusion.5 = bf16[128,32,4,128] fusion(%a)",
                 paged + "/body/jit(_take)/gather", 7),
        metadata(5, "%convolution.3 = f32[8,4,512,7] convolution(%a)",
                 paged + "/body/bthrd,bshd->bhrts/dot_general", 7),
        metadata(6, "%while.4 = (f32[8]) while(%t)",
                 chunk + f"mla/attend/jit(chunked_paged_attention)/{scope}"
                 "/while", 7),
        metadata(7, "%fusion.9 = f32[8,152064] fusion(%a)",
                 "jit(_decode_chunk_impl)/while/body/dot_general", 7),
        metadata(8, "%fusion.5 = f32[8] fusion(%a)",
                 "jit(update)/dot_general", 8),
    ]
    calls = [0, 200 * US]
    modules = line("XLA Modules", [(1, at, 100 * US) for at in calls]
                   + [(2, 400 * US, 100 * US)])
    ops = line("XLA Ops", [
        event for at in calls for event in (
            (3, at + 10 * US, 30 * US), (4, at + 12 * US, 8 * US),
            (5, at + 22 * US, 16 * US), (6, at + 50 * US, 10 * US),
            (7, at + 60 * US, 35 * US))] + [(8, 410 * US, 50 * US)])
    space = plane("/device:TPU:0", metas, [modules, ops]) \
        + plane("/host:CPU", [], [line("python", [])])
    path = (tmp_path / ".perfbench_trace" / "cell" / "plugins" / "profile"
            / "run" / "host.xplane.pb")
    path.parent.mkdir(parents=True)
    path.write_bytes(space)
    return path


def test_paged_attend_share_is_the_loops_union_over_the_decode_chunk(tmp_path):
    ctx = ctx_for(tmp_path, by_hand(tmp_path))
    # (30 + 10) us of every 100 us call; the update's fusion.5 is not the
    # decode chunk's, and the update is not in the denominator
    assert paged_attend_share.read(ctx) == pytest.approx(40.0)


def test_a_parents_decode_chunk_has_no_such_scope(tmp_path):
    ctx = ctx_for(tmp_path, by_hand(tmp_path, scope="elsewhere"))
    assert paged_attend_share.read(ctx) is None
    assert paged_attend_share.read(ctx_for(tmp_path / "nothing")) is None
