"""The ten readers of the device half of tracing (PR 36) on traces small
enough to work out by hand (the writer of ``test_scope_readers.py``, which
came with the hybrid cell and is not edited): milliseconds under
``decode/*`` a decode step and under ``evo/*`` a generation, the four
kernels' shares of the bf16 peak from each execution's own result type, and
``None`` on a parent's trace, which has neither the scopes nor the names."""

import types

import pytest

from perfbench import xplane
from perfbench.layer_metrics import (_kernels, decode_ffn_ms, decode_head_ms,
                                     decode_proj_ms, flash_bwd_roofline,
                                     flash_fwd_roofline,
                                     fused_loss_dh_roofline,
                                     fused_loss_fwd_roofline, gen_rollout_ms,
                                     gen_shuffle_ms, gen_update_ms)
from perfbench.tests.test_scope_readers import US, line, metadata, plane

PEAK = 1e12  # FLOP/s: a microsecond holds a MFLOP
QWEN = {"hidden_size": 8, "vocab_size": 100, "num_attention_heads": 2,
        "serving": {"decode_chunk": 4}}
#: latent attention's published keys: q and k 192 wide, v 128
KANANA = dict(QWEN, head_dim=64, qk_head_dim=192, v_head_dim=128)


def write(tmp_path, metas, modules, ops, planes=1):
    device = [line("XLA Modules", modules), line("XLA Ops", ops)]
    space = b"".join(plane(f"/device:TPU:{i}", metas, device)
                     for i in range(planes)) \
        + plane("/host:CPU", [], [line("python", [])])
    path = (tmp_path / ".perfbench_trace" / "cell" / "plugins" / "profile"
            / "run" / "host.xplane.pb")
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(space)
    return path


def ctx_for(tmp_path, path, config=QWEN, chips=1):
    cell = types.SimpleNamespace(root=tmp_path, name="cell", chips=chips,
                                 config=config)
    return types.SimpleNamespace(cell=cell, trace=xplane.load(path),
                                 peaks={"bf16_flops_per_s": PEAK})


# -- the decode chunk --------------------------------------------------------
def decode_trace(tmp_path, named=True):
    """Two calls of ``jit__decode_chunk_impl`` (id 7) of 100 us, chunks of
    4 steps. A call: the layer loop's ``while.2`` 5..80 (no scope of its
    own) with, inside it, the q/k/v projection ``fusion.1`` 10..14 and
    ``wo`` ``fusion.2`` 30..32 under ``decode/proj``, the attention loop
    ``while.3`` 15..30 under ``paged/attend``, the expert layer's
    ``fusion.3`` 40..70 under ``decode/ffn`` and ``moe/experts`` both; after
    the loop the head ``fusion.4`` 80..92 and the sampler's ``sort.1``
    92..96 under ``decode/head``. A ``jit_update`` (8) has a ``fusion.4``
    of its own under no scope."""
    chunk = "jit(_decode_chunk_impl)/while/body/"
    layer = chunk + "while/body/closed_call/"
    scope = (lambda s: s) if named else (lambda s: "")
    metas = [
        metadata(1, "jit__decode_chunk_impl(7)"),
        metadata(2, "jit_update(8)"),
        metadata(3, "%while.2 = (f32[8]) while(%t)", chunk + "while", 7),
        metadata(4, "%fusion.1 = f32[8,48] fusion(%a)",
                 layer + scope("decode/proj/") + "dot_general",
                 7),
        metadata(5, "%fusion.2 = f32[8,32] fusion(%a)",
                 layer + scope("decode/proj/") + "add", 7),
        metadata(6, "%while.3 = (f32[8]) while(%t)",
                 layer + "jit(chunked_paged_attention)/paged/attend/while", 7),
        metadata(7, "%fusion.3 = f32[8,32] fusion(%a)",
                 layer + scope("decode/ffn/")
                 + "moe/experts/ragged_dot", 7),
        metadata(8, "%fusion.4 = f32[8,100] fusion(%a)",
                 chunk + scope("decode/head/") + "dot_general",
                 7),
        metadata(9, "%sort.1 = (f32[8,100]) sort(%a)",
                 chunk + scope("decode/head/")
                 + "jit(_categorical)/sort", 7),
        metadata(10, "%fusion.4 = f32[8] fusion(%a)",
                 "jit(update)/dot_general", 8),
    ]
    calls = [0, 200 * US]
    modules = [(1, at, 100 * US) for at in calls] + [(2, 400 * US, 100 * US)]
    ops = [event for at in calls for event in (
        (3, at + 5 * US, 75 * US), (4, at + 10 * US, 4 * US),
        (6, at + 15 * US, 15 * US), (5, at + 30 * US, 2 * US),
        (7, at + 40 * US, 30 * US), (8, at + 80 * US, 12 * US),
        (9, at + 92 * US, 4 * US))] + [(10, 410 * US, 50 * US)]
    return write(tmp_path, metas, modules, ops)


def test_decode_scopes_read_milliseconds_a_step(tmp_path):
    ctx = ctx_for(tmp_path, decode_trace(tmp_path))
    # two calls x four steps; a call holds 16 us of head, 30 of ffn, 6 of proj
    assert decode_head_ms.read(ctx) == pytest.approx(16e-3 / 4)
    assert decode_ffn_ms.read(ctx) == pytest.approx(30e-3 / 4)
    assert decode_proj_ms.read(ctx) == pytest.approx(6e-3 / 4)


def test_a_parents_decode_chunk_has_no_such_scopes(tmp_path):
    ctx = ctx_for(tmp_path, decode_trace(tmp_path, named=False))
    for reader in (decode_head_ms, decode_ffn_ms, decode_proj_ms):
        assert reader.read(ctx) is None
    nothing = types.SimpleNamespace(cell=types.SimpleNamespace(
        root=tmp_path / "nothing", name="cell", config=QWEN), trace=None)
    assert decode_head_ms.read(nothing) is None


# -- the generation program --------------------------------------------------
def generation_trace(tmp_path, named=True):
    """Three calls of ``jit_generation`` (id 5) of 1000 us: the rollout's
    ``while.1`` 0..50 with a body ``fusion.1`` inside, under the vmapped
    scope; per call two epochs, each a ``sort.3`` of 100 us under
    ``evo/shuffle`` and the minibatch scan ``while.4`` of 300 us under
    ``evo/update`` with a ``fusion.7`` inside it; then tournament and
    mutation, ``fusion.9`` 900..950, under no scope."""
    gen = "jit(generation)/"
    scope = (lambda s: s) if named else (lambda s: "")
    epoch = gen + "vmap(while)/body/"
    metas = [
        metadata(1, "jit_generation(5)"),
        metadata(2, "%while.1 = (f32[8]) while(%t)",
                 gen + scope("vmap(evo/rollout)/") + "while",
                 5),
        metadata(3, "%fusion.1 = f32[64,4096] fusion(%a)",
                 gen + scope("vmap(evo/rollout)/")
                 + "while/body/dot_general", 5),
        metadata(4, "%sort.3 = (f32[64,524288]) sort(%a)",
                 epoch + scope("vmap(evo/shuffle)/") + "sort",
                 5),
        metadata(5, "%while.4 = (f32[8]) while(%t)",
                 epoch + scope("vmap(evo/update)/") + "while",
                 5),
        metadata(6, "%fusion.7 = f32[64,131072,64] fusion(%a)",
                 epoch + scope("vmap(evo/update)/")
                 + "while/body/transpose(jvp(dot_general))", 5),
        metadata(7, "%fusion.9 = f32[64,64] fusion(%a)", gen + "select_n", 5),
    ]
    calls = [0, 1000 * US, 2000 * US]
    modules = [(1, at, 1000 * US) for at in calls]
    ops = [event for at in calls for event in (
        (2, at, 50 * US), (3, at + 10 * US, 30 * US),
        (4, at + 50 * US, 100 * US), (5, at + 150 * US, 300 * US),
        (6, at + 200 * US, 200 * US),
        (4, at + 450 * US, 100 * US), (5, at + 550 * US, 300 * US),
        (6, at + 600 * US, 200 * US), (7, at + 900 * US, 50 * US))]
    return write(tmp_path, metas, modules, ops)


def test_generation_scopes_read_milliseconds_a_generation(tmp_path):
    ctx = ctx_for(tmp_path, generation_trace(tmp_path))
    assert gen_rollout_ms.read(ctx) == pytest.approx(0.050)
    assert gen_shuffle_ms.read(ctx) == pytest.approx(0.200)
    assert gen_update_ms.read(ctx) == pytest.approx(0.600)  # a union
    # another path: the metadata of a file is read once a path
    parent = ctx_for(tmp_path / "parent",
                     generation_trace(tmp_path / "parent", named=False))
    for reader in (gen_rollout_ms, gen_shuffle_ms, gen_update_ms):
        assert reader.read(parent) is None


# -- the kernels --------------------------------------------------------------
def learn_trace(tmp_path, named=True, planes=1):
    """``jit_logprobs`` runs twice at two shapes — two PROGRAMS of one name
    (ids 21, 22) whose forward kernel has ONE operation name,
    ``fused_loss_fwd.1``, at N 1000 (20 us) and N 3000 (40 us) — and
    ``jit_update`` (23) once: the forward under jvp at N 1000 (25 us), dH
    (50 us), and flash attention's three at BH 4, Tp 100: forward 10 us, dq
    20 us, dkv 30 us. A ``fusion.1`` of the update and a decode chunk's
    ``flash_fwd.1`` are nobody's."""
    name = (lambda s: s) if named else (lambda s: "checkpoint")
    fwd = "(f32[%d,1]{1,0}, f32[%d,1]{1,0}) custom-call(%%h)"
    metas = [
        metadata(1, "jit_logprobs(21)"), metadata(2, "jit_logprobs(22)"),
        metadata(3, "jit_update(23)"), metadata(4, "jit__decode_chunk_impl(24)"),
        metadata(5, f"%{name('fused_loss_fwd')}.1 = " + fwd % (1000, 1000),
                 "jit(logprobs)/pallas_call", 21),
        metadata(6, f"%{name('fused_loss_fwd')}.1 = " + fwd % (3000, 3000),
                 "jit(logprobs)/pallas_call", 22),
        metadata(7, f"%jvp_{name('fused_loss_fwd')}_.1 = " + fwd % (1000, 1000),
                 "jit(update)/jvp/pallas_call", 23),
        metadata(8, f"%transpose_jvp_{name('fused_loss_dh')}__.1 = "
                 "bf16[1000,8]{1,0} custom-call(%h)",
                 "jit(update)/transpose/pallas_call", 23),
        metadata(9, f"%{name('flash_fwd')}.3 = (bf16[4,100,128]{{2,1,0}}, "
                 "f32[4,100,1]{2,1,0}) custom-call(%q)",
                 "jit(update)/checkpoint/pallas_call", 23),
        metadata(10, f"%{name('flash_dq')}.3 = bf16[4,100,192]{{2,1,0}} "
                 "custom-call(%q)", "jit(update)/transpose/pallas_call", 23),
        metadata(11, f"%{name('flash_dkv')}.3 = (bf16[4,100,192]{{2,1,0}}, "
                 "bf16[4,100,128]{2,1,0}) custom-call(%q)",
                 "jit(update)/transpose/pallas_call", 23),
        metadata(12, "%fusion.1 = bf16[1000,8] fusion(%a)",
                 "jit(update)/dot_general", 23),
        metadata(13, "%flash_fwd.1 = (bf16[9,900,128], f32[9,900,1]) "
                 "custom-call(%q)", "jit(_decode_chunk_impl)/pallas_call", 24),
    ]
    modules = [(1, 0, 100 * US), (2, 100 * US, 100 * US),
               (3, 200 * US, 300 * US), (4, 600 * US, 100 * US)]
    ops = [(5, 10 * US, 20 * US), (6, 110 * US, 40 * US),
           (7, 210 * US, 25 * US), (9, 240 * US, 10 * US),
           (12, 260 * US, 100 * US), (8, 370 * US, 50 * US),
           (10, 420 * US, 20 * US), (11, 440 * US, 30 * US),
           (13, 610 * US, 50 * US)]
    return write(tmp_path, metas, modules, ops, planes)


def test_a_kernels_flops_come_from_each_executions_own_result(tmp_path):
    ctx = ctx_for(tmp_path, learn_trace(tmp_path))
    ndv = 8 * 100  # D x V
    # three executions, two of them one NAME at different N
    assert fused_loss_fwd_roofline.read(ctx) == pytest.approx(
        100 * 2 * (1000 + 3000 + 1000) * ndv / 85e-6 / PEAK)
    assert fused_loss_dh_roofline.read(ctx) == pytest.approx(
        100 * 4 * 1000 * ndv / 50e-6 / PEAK)
    table = _kernels.operations(str(_kernels._scopes.cell_trace(ctx)))
    assert table[(21, "fused_loss_fwd.1")].result.startswith("(f32[1000,1]")
    assert table[(22, "fused_loss_fwd.1")].result.startswith("(f32[3000,1]")


def test_flash_attention_is_counted_causal_at_the_configurations_widths(
        tmp_path):
    path = learn_trace(tmp_path)
    square = 4 * 100 * 100  # BH x Tp^2
    qwen = ctx_for(tmp_path, path)  # heads of 8 / 2 = 4, q = k = v
    assert flash_fwd_roofline.read(qwen) == pytest.approx(
        100 * square * (4 + 4) / 10e-6 / PEAK)
    assert flash_bwd_roofline.read(qwen) == pytest.approx(
        100 * square * (4 * 4 + 3 * 4) / 50e-6 / PEAK)
    kanana = ctx_for(tmp_path, path, KANANA)
    assert flash_fwd_roofline.read(kanana) == pytest.approx(
        100 * square * (192 + 128) / 10e-6 / PEAK)
    assert flash_bwd_roofline.read(kanana) == pytest.approx(
        100 * square * (4 * 192 + 3 * 128) / 50e-6 / PEAK)


def test_on_a_mesh_a_chips_shapes_over_a_chips_seconds(tmp_path):
    one = ctx_for(tmp_path / "one", learn_trace(tmp_path / "one"))
    four = ctx_for(tmp_path / "four", learn_trace(tmp_path / "four", planes=4),
                   chips=4)
    for reader in (fused_loss_fwd_roofline, fused_loss_dh_roofline,
                   flash_fwd_roofline, flash_bwd_roofline):
        assert reader.read(four) == pytest.approx(reader.read(one))


def test_a_parents_kernels_have_no_names(tmp_path):
    ctx = ctx_for(tmp_path, learn_trace(tmp_path, named=False))
    for reader in (fused_loss_fwd_roofline, fused_loss_dh_roofline,
                   flash_fwd_roofline, flash_bwd_roofline):
        assert reader.read(ctx) is None


# -- the command ---------------------------------------------------------------
def test_top_scope_is_the_outermost_name_the_program_put_on():
    top = _kernels.top_scope
    assert top("jit(_decode_chunk_impl)/while/body/while/body/closed_call/"
               "decode/ffn/moe/experts/ragged_dot:") \
        == "decode/ffn"
    assert top("jit(generation)/vmap(evo/rollout)/while/body/"
               "dot_general") == "evo/rollout"
    assert top("jit(update)/transpose(jvp(ssm/scan_bwd))/while/body/mul") \
        == "ssm/scan_bwd"
    assert top("jit(f)/while/body/closed_call/bthrd,bshd->bhrts/dot_general") \
        == ""
    assert top("jit(small_step)/while:") == ""


def test_the_summary_lists_scopes_and_kernels_by_program(tmp_path):
    path = decode_trace(tmp_path / "a")
    found = _kernels.summary(xplane.load(path), _kernels.operations(str(path)))
    chunk = found["jit__decode_chunk_impl"]
    assert chunk["calls"] == 2 and chunk["seconds"] == pytest.approx(200e-6)
    assert chunk["scopes"] == pytest.approx({
        "decode/head": 32e-6, "decode/ffn": 60e-6, "decode/proj": 12e-6,
        "paged/attend": 30e-6})
    assert found["jit_update"]["scopes"] == {}
    path = learn_trace(tmp_path / "b", planes=2)
    found = _kernels.summary(xplane.load(path), _kernels.operations(str(path)),
                             QWEN)
    fwd = found["jit_logprobs"]["kernels"]["fused_loss_fwd"]
    assert fwd["executions"] == pytest.approx(2)
    assert fwd["seconds"] == pytest.approx(60e-6)
    assert fwd["tflops"] == pytest.approx(2 * 4000 * 800 / 60e-6 / 1e12)
    assert sorted(found["jit_update"]["kernels"]) == [
        "flash_dkv", "flash_dq", "flash_fwd", "fused_loss_dh",
        "fused_loss_fwd"]
