"""Device time per program scope, on a small hand-built trace and HLO
text whose answers are counted by hand."""
import os
import types

import pytest

from bench import scopes
from bench.harness import metric_reader
from bench.tests.tiny import BENCH
from bench.xtrace import WINDOW, Event

HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %neg.9 = f32[4]{0} negate(%param_0), metadata={op_name="jit(train_step)/model/mlp/neg"}
}

%body.2 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %dynamic-slice_bitcast_fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(model/blocks))/while/body/dynamic_slice"}
  %fusion.4 = f32[4]{0} fusion(%dynamic-slice_bitcast_fusion.3), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(model/blocks))/while/body/closed_call/checkpoint/rematted_computation/model/attn/dot_general"}
  %copy.5 = f32[4]{0} copy(%fusion.4)
  ROOT %tuple.6 = (s32[], f32[4]{0}) tuple(%p, %copy.5)
}

%cond.3 (p.1: (s32[], f32[4])) -> pred[] {
  %p.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %compare.7 = pred[] compare(%p.1, %p.1), direction=LT, metadata={op_name="jit(train_step)/jvp(model/blocks)/while/cond/lt"}
}

ENTRY %main.9 (Arg_0: f32[4]) -> f32[4] {
  %Arg_0 = f32[4]{0} parameter(0)
  %copy.8 = f32[4]{0} copy(%Arg_0), metadata={op_name="state.params"}
  %tuple.1 = (s32[], f32[4]{0}) tuple(%copy.8, %copy.8)
  %while.10 = (s32[], f32[4]{0}) while(%tuple.1), condition=%cond.3, body=%body.2, metadata={op_name="jit(train_step)/jvp(model/blocks)/while"}
  %_dct_project.11 = (f32[1,8,8]{2,1,0}, f32[1,1,8]{2,1,0}) custom-call(%Arg_0), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[1,8,8]{2,1,0}}, metadata={op_name="jit(train_step)/opt/update/opt/select_project/jit(_dct_project)/pallas_call"}
  %sub.12 = f32[4]{0} subtract(%Arg_0, %Arg_0), metadata={op_name="jit(train_step)/opt/update/opt/backproject/sub"}
  %fusion.1 = f32[4]{0} fusion(%sub.12), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/opt/update/transpose"}
  %add.13 = f32[4]{0} add(%Arg_0, %Arg_0), metadata={op_name="jit(train_step)/add"}
  ROOT %fusion.14 = f32[4]{0} fusion(%add.13), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/step/apply/add"}
}
"""


def ev(name, start, end):
    return Event(name, start, end)


def op(instr, start, end):
    return ev(f"%{instr} = f32[4]{{0}} fusion(%x), kind=kLoop", start, end)


def profile(host=None):
    # window 0..10 s. Device 0 runs the step at [1,4] and [5,8], and the
    # data pipeline's own module at [4.2,4.8], whose fusion.1 shares its
    # name with an instruction of the step; device 1 runs the step at
    # [1,4] and [5,8] too.
    dev0 = [op("while.10", 1, 3), op("dynamic-slice_bitcast_fusion.3", 1,
                                      1.5),
            op("fusion.4", 1.5, 2.5), op("copy.5", 2.5, 3),
            op("_dct_project.11", 3, 3.5), op("add.13", 3.5, 4),
            op("fusion.1", 4.3, 4.6),
            op("fusion.4", 5, 6), op("sub.12", 6, 7), op("fusion.14", 7, 8),
            op("fusion.4", 11, 12)]
    dev1 = [op("fusion.4", 1, 3), op("fusion.1", 5, 6)]
    mods0 = [ev("jit_train_step(7)", 1, 4), ev("jit_batch(9)", 4.2, 4.8),
             ev("jit_train_step(7)", 5, 8), ev("jit_train_step(7)", 11, 12)]
    mods1 = [ev("jit_train_step", 1, 4), ev("jit_train_step", 5, 8)]
    if host is None:
        host = [ev("train/data_wait", 0.2, 0.9), ev("train/dispatch", 0.9,
                                                    1.2),
                ev("train/host_sync", 1.2, 4.0),
                ev("train/data_wait", 4.0, 4.5), ev("train/dispatch", 4.5,
                                                    5.1)]
    return scopes.from_events({0: dev0, 1: dev1}, {0: mods0, 1: mods1},
                              [ev(WINDOW, 0, 10)] + host)


def test_innermost_scope_assigns_an_op_name():
    assert scopes.scope_of("jit(train_step)/transpose(jvp(model/blocks))/"
                           "while/body/closed_call/checkpoint/"
                           "rematted_computation/model/attn/dot_general") \
        == "model/attn"
    assert scopes.scope_of("jit(train_step)/opt/update/opt/select_project/"
                           "jit(_dct_project)/pallas_call") \
        == "opt/select_project"
    assert scopes.scope_of("jit(train_step)/add") is None
    assert scopes.scope_of("jit(my_step)/x") is None        # not a scope


def test_hlo_ops_inherit_scopes_where_the_compiler_made_the_op():
    ops = scopes.hlo_ops(HLO)
    assert "neg.9" not in ops                  # inside a fusion: no event
    assert scopes.module_name(HLO) == "jit_train_step"
    want = {"dynamic-slice_bitcast_fusion.3": "model/blocks",
            "fusion.4": "model/attn",
            "copy.5": "model/blocks",          # the loop that runs it
            "copy.8": "model/blocks",          # sets up the loop's carry
            "_dct_project.11": "opt/select_project",
            "sub.12": "opt/backproject", "fusion.1": "opt/update",
            "add.13": None,                    # the program's, unscoped
            "fusion.14": "step/apply"}
    assert {k: ops[k].scope for k in want} == want
    assert ops["_dct_project.11"].opcode == "custom-call"
    assert ops["while.10"].opcode == "while"


def test_table_per_scope_per_step_and_chip():
    t = scopes.table(profile(), HLO)
    assert t.steps == 2
    ms = {k: pytest.approx(v) for k, v in {
        "model/attn": 1000.0,             # (2.0 + 2.0) s / 2 steps / 2
        "model/blocks": 250.0, "opt/select_project": 125.0,
        "opt/backproject": 250.0,
        "opt/update": 250.0,              # device 1 only: the pipeline's
        "step/apply": 250.0,              # fusion.1 is another module's
        None: 125.0}.items()}
    assert t.ms == ms
    assert sum(t.ms.values()) == pytest.approx(t.busy_ms)
    assert t.busy_ms == pytest.approx((6.0 + 3.0) / 2 / 2 * 1e3)
    assert t.ops["model/blocks"] == {
        "dynamic-slice_bitcast_fusion": pytest.approx(125.0),
        "copy": pytest.approx(125.0)}
    one = scopes.table(profile(), HLO, devices=[1], steps=2)
    assert one.ms == {"model/attn": pytest.approx(1000.0),
                      "opt/update": pytest.approx(500.0)}


def test_table_is_none_without_scopes_or_step_module():
    plain = HLO.replace("model/", "m_").replace("opt/", "o_").replace(
        "step/", "s_")
    assert scopes.table(profile(), plain) is None
    other = HLO.replace("HloModule jit_train_step", "HloModule jit_other")
    assert scopes.table(profile(), other) is None


def test_loop_gap_is_idle_time_under_the_loop_spans():
    # idle on device 0: [0,1] [4,4.3] [4.6,5] [8,10]; device 1: [0,1]
    # [3,5] [6,10]. The spans cover [0.2,1.2] and [4.0,5.1]: 0.8 + 0.3 +
    # 0.4 s on device 0, 0.8 + 1.0 s on device 1; host_sync is not one
    gap = scopes.loop_gap_ms(profile())
    assert gap == pytest.approx((1.5 + 1.8) / 2 / 2 * 1e3)
    assert scopes.loop_gap_ms(profile(), devices=[0], steps=3) == \
        pytest.approx(1.5 / 3 * 1e3)
    assert scopes.loop_gap_ms(profile(host=[ev("other", 0, 1)])) is None


def test_strip_metadata_keeps_the_instructions():
    text = HLO + "\nFileNames\n1 \"a.py\"\n\nStackFrames\n1 {x}\n\n"
    plain = scopes.strip_metadata(text)
    assert "op_name" not in plain and "a.py" not in plain
    assert plain == scopes.strip_metadata(text.replace("model/attn", "z"))
    assert "%fusion.4 = f32[4]{0} fusion(%dynamic-slice_bitcast_fusion.3)" \
        in plain


READERS = {"model_attn_ms": 1000.0, "model_mlp_ms": 0.0,
           "layer_scan_ms": 250.0, "opt_step_ms": 625.0,
           "unscoped_ms": 125.0, "loop_gap_ms": 825.0}


def ctx(window=(0, 10)):
    return types.SimpleNamespace(trace=types.SimpleNamespace(window=window),
                                 devices=[0, 1], steps=2)


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers(monkeypatch, name):
    read = metric_reader(os.path.dirname(BENCH), name)
    monkeypatch.setattr(scopes, "current", lambda d=None: (profile(), HLO))
    assert read(ctx()) == pytest.approx(READERS[name])
    # a trace left from another run: another window
    assert read(ctx(window=(0, 9))) is None
    monkeypatch.setattr(scopes, "current", lambda d=None: None)
    assert read(ctx()) is None                 # no trace or no HLO
    monkeypatch.setattr(scopes, "current", lambda d=None: (
        profile(host=[]), HLO.replace("/", "_")))
    assert read(ctx()) is None                 # no scopes, no annotations


def test_current_needs_both_files(tmp_path, monkeypatch):
    assert scopes.current(str(tmp_path)) is None
    (tmp_path / "plugins").mkdir()
    (tmp_path / "plugins" / "x.xplane.pb").write_bytes(b"")
    assert scopes.current(str(tmp_path)) is None          # no HLO
    monkeypatch.setattr(scopes, "TRACE_DIR", str(tmp_path))
    read = metric_reader(os.path.dirname(BENCH), "unscoped_ms")
    assert read(ctx()) is None
