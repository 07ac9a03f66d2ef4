"""The in-memory span recorder (repro.common.spans): off it records
nothing, on it records the tree of spans, each span's round and the
counters by round."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import spans


@pytest.fixture
def recorder():
    spans.drain()
    spans.enable()
    try:
        yield
    finally:
        spans.disable()
        spans.drain()


class _Program:
    name = "prog"

    def __call__(self, x):
        return x + 1


def _touch_everything():
    with spans.round_span(0) as r:
        with spans.span("fl.feed"):
            spans.count("host_syncs")
        out = spans.dispatch(_Program(), jnp.ones(3))
        host = spans.fetch(out, "x")
        seconds = r.close()
    return host, seconds


def test_off_it_records_nothing_and_the_round_span_still_times():
    spans.drain()
    host, seconds = _touch_everything()
    np.testing.assert_array_equal(host, np.full(3, 2.0))
    assert seconds > 0
    rec = spans.drain()
    assert rec.spans == [] and rec.counters == {} and rec.programs == {}


def test_on_it_records_the_tree_rounds_and_counters(recorder):
    host, seconds = _touch_everything()
    rec = spans.drain()
    by = {s.name: s for s in rec.spans}
    assert set(by) == {"fl.round", "fl.feed", "fl.dispatch", "fl.sync"}
    root = by["fl.round"]
    assert root.parent is None and root.round == 0
    for name in ("fl.feed", "fl.dispatch", "fl.sync"):
        assert by[name].parent == root.id and by[name].round == 0
        assert root.start_ns <= by[name].start_ns <= by[name].end_ns \
            <= root.end_ns
    assert by["fl.dispatch"].attrs == {"program": "prog"}
    assert by["fl.sync"].attrs == {"what": "x"}
    assert seconds == pytest.approx((root.end_ns - root.start_ns) * 1e-9)
    # one count by hand, one by the device read
    assert rec.counters == {(0, "host_syncs"): 2}
    assert list(rec.programs) == ["prog"]


def test_a_host_value_costs_no_sync(recorder):
    with spans.round_span(3):
        spans.fetch(np.ones(2), "host")
        spans.fetch(1.5, "scalar")
    rec = spans.drain()
    assert [s.name for s in rec.spans] == ["fl.round"]
    assert rec.counters == {}


def test_rounds_nest_their_spans_and_a_span_left_open_is_closed_above():
    spans.drain()
    spans.enable()
    try:
        r0 = spans.round_span(7)
        inner = spans.span("fl.chunk", chunk=0)
        inner.__enter__()               # its owner never closes it
        r0.close()                      # closing the round pops it too
        with spans.span("fl.feed"):
            pass
        rec = spans.drain()
    finally:
        spans.disable()
        spans.drain()
    by = {s.name: s for s in rec.spans}
    assert set(by) == {"fl.round", "fl.feed"}
    assert by["fl.feed"].parent is None and by["fl.feed"].round is None


def test_a_round_begun_while_off_is_not_recorded():
    spans.drain()
    r = spans.round_span(1)
    spans.enable()
    try:
        r.close()
        rec = spans.drain()
    finally:
        spans.disable()
    assert rec.spans == []


HLO = """HloModule jit_round_step, is_scheduled=true

%body.1 (arg.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg.1 = (s32[], f32[8]) parameter(0)
  %gte.1 = f32[8] get-tuple-element((s32[], f32[8]) %arg.1), index=1
  %copy.2 = f32[8] copy(f32[8] %gte.1)
  ROOT %tuple.1 = (s32[], f32[8]) tuple(s32[] %c.1, f32[8] %copy.2)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> (f32[8], f32[]) {
  %Arg_0.1 = f32[8] parameter(0), metadata={op_name="params"}
  %fusion.1 = f32[8] fusion(f32[8] %Arg_0.1), kind=kLoop, calls=%fc.1, metadata={op_name="jit(round_step)/jit(main)/fl/select/iota"}
  %while.5 = (s32[], f32[8]) while((s32[], f32[8]) %t.0), condition=%cond.1, body=%body.1, metadata={op_name="jit(round_step)/fl/local_train/vmap(fl/codec)/while"}
  %copy-start.3 = (f32[8]{0:T(128)}, f32[8]{0:T(128)S(1)}, u32[]{:S(2)}) copy-start(%fusion.1)
  %copy-done.3 = f32[8]{0:T(128)S(1)} copy-done(%copy-start.3)
  %masked_agg.1 = f32[8] custom-call(f32[8] %copy-done.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_step)/fl/aggregate/pallas_call"}
  ROOT %tuple.9 = (f32[8], f32[]) tuple(f32[8] %masked_agg.1, f32[] %c.2)
}
"""


def test_stages_come_from_scopes_and_the_rest_inherit_them():
    from repro.analysis.compileguard import hlo_stages
    module, direct = hlo_stages(HLO, inherit=False)
    assert module == "jit_round_step"
    # the innermost fl/ scope, through a transform's parentheses
    assert direct == {"fusion.1": "select", "while.5": "codec",
                      "masked_agg.1": "aggregate"}
    _, stages = hlo_stages(HLO)
    # the loop body takes its while's stage; the async copy (a tiled
    # layout's brackets before its operands) its user's
    assert {k: stages[k] for k in ("gte.1", "copy.2", "tuple.1", "arg.1")} \
        == dict.fromkeys(("gte.1", "copy.2", "tuple.1", "arg.1"), "codec")
    assert stages["copy-done.3"] == stages["copy-start.3"] == "aggregate"
    assert stages["Arg_0.1"] == "select"       # its user's
    assert stages["tuple.9"] == "aggregate"    # its first operand's
    assert {k: stages[k] for k in direct} == direct
