"""The program's spans, counters and stage maps joined with a device
trace (program_trace.py), on intervals, spans and stage maps built by
hand, and on two rounds of the vgg16-cifar10.sync-t25 cell recorded on
a TPU v5e with the recorder on."""
import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import program_trace as pt  # noqa: E402
from benchmarks.chip import trace  # noqa: E402
from repro.common.spans import Span  # noqa: E402


def _ops(*events):
    return trace.Ops(np.asarray([s for s, _, _ in events], np.int64),
                     np.asarray([e for _, e, _ in events], np.int64),
                     [n for _, _, n in events])


def _span(i, parent, name, s, e, **attrs):
    return Span(i, parent, name, s, e, 0, attrs)


@pytest.fixture
def hand():
    """One round on one chip.  Harness: ``bench.fit`` [0, 90),
    ``bench.sync`` [90, 100).  Program: the round [2, 88), its feed
    [2, 20) (built [2, 12), copied [12, 20)), the dispatch of
    ``round_step`` [20, 22) and the wait on the selection [22, 85).  On
    the device, ``jit_round_step`` runs [25, 86): local training, the
    aggregate, a copy with no scope and the finalize; then, in another
    module, an op whose instruction name the round step also has."""
    dev = _ops((25, 40, "%while.5 = (f32[8]) while(...)"),
               (40, 50, "%fusion.1 = f32[8] fusion(...)"),
               (50, 55, "%copy.3 = f32[8] copy(...)"),
               (55, 86, "%fusion.9 = f32[] fusion(...)"),
               (90, 92, "%fusion.1 = u32[2] fusion(...)"))
    red = trace.Reduced(0, 100, [dev], [(0, 90, "bench.fit"),
                                        (90, 100, "bench.sync")])
    modules = [[(90, 92, "jit__threefry_split"),
                (25, 86, "jit_round_step")]]
    prog = [_span(0, None, "fl.round", 2, 88),
            _span(1, 0, "fl.feed", 2, 20),
            _span(2, 1, "fl.feed.build", 2, 12),
            _span(3, 1, "fl.feed.h2d", 12, 20),
            _span(4, 0, "fl.dispatch", 20, 22, program="round_step"),
            _span(5, 0, "fl.sync", 22, 85, what="sel")]
    maps = {"round_step": {"jit_round_step": {
        "while.5": "local_train", "fusion.1": "aggregate",
        "fusion.9": "finalize"}}}
    return pt.ProgramTrace.build(red, modules, prog,
                                 {(0, "host_syncs"): 3}, maps)


def test_each_op_takes_the_stage_of_its_module_and_instruction(hand):
    assert hand.stages == [["local_train", "aggregate", "unstaged",
                            "finalize", "unstaged"]]
    assert hand.rounds() == [0]


def test_stage_times_are_unions_per_round(hand):
    assert pt.local_train_ms(hand) == pytest.approx(15e-6)
    assert pt.aggregate_ms(hand) == pytest.approx(41e-6)
    assert pt.codec_ms(hand) is None
    assert hand.stage_totals() == pytest.approx(
        {"aggregate": 10e-6, "finalize": 31e-6, "local_train": 15e-6,
         "unstaged": 7e-6})
    assert hand.unstaged_share() == pytest.approx(7 / 63)


def test_idle_in_the_feed_and_gaps_outside_every_program_span(hand):
    # idle [0, 25) runs through the feed's build and copy, the dispatch
    # and the wait; [86, 90) through the round's end and bench.fit after
    # it, outside every program span; [92, 100) lies in bench.sync
    assert hand.idle_gaps() == [[(0, 25), (86, 90), (92, 100)]]
    assert hand.idle_pieces() == [[
        (0, 2, "bench.fit"), (2, 12, "fl.feed.build"),
        (12, 20, "fl.feed.h2d"), (20, 22, "fl.dispatch"),
        (22, 25, "fl.sync"), (86, 88, "fl.round"), (88, 90, "bench.fit"),
        (92, 100, "bench.sync")]]
    assert pt.feed_idle_ms(hand) == pytest.approx(18e-6)
    assert hand.idle_by_span() == pytest.approx(
        {"fl.feed.build": 10e-6, "fl.feed.h2d": 8e-6, "bench.sync": 8e-6,
         "bench.fit": 4e-6, "fl.sync": 3e-6, "fl.dispatch": 2e-6,
         "fl.round": 2e-6})
    assert hand.named_idle_share() == pytest.approx(25 / 29)
    # the breakdown names a gap by its midpoint, as trace.py does
    gaps = {n: round(s * 1e9) for n, s in hand.red.breakdown()["idle_gaps"]}
    assert gaps == {"fl.feed.h2d": 25, "bench.sync": 8, "bench.fit": 4}


def test_host_syncs_and_the_dispatch_lead(hand):
    assert pt.host_syncs_per_round(hand) == 3.0
    assert hand.leads() == {"round_step": [(20, 5)]}


def test_nothing_to_read_gives_no_reading():
    red = trace.Reduced(0, 100, [_ops((10, 20, "%fusion.1 = f32[8]"))],
                        [(0, 100, "bench.fit")])
    bare = pt.ProgramTrace.build(red, [[(10, 20, "jit_round_step")]], [],
                                 {}, {})
    assert all(f(bare) is None for f in pt.METRICS.values())
    assert bare.stages == [["unstaged"]]
    assert bare.named_idle_share() == 0.0


DATA = ROOT / "benchmarks" / "chip" / "tests" / "data"
RECORDED = DATA / "vgg16-cifar10.sync-t25.program.xplane.pb.gz"
PROGRAM = DATA / "vgg16-cifar10.sync-t25.program.json.gz"


@pytest.fixture(scope="module")
def recorded():
    """Two traced rounds of vgg16-cifar10.sync-t25 on a TPU v5e with the
    recorder on (trace_program.py --rounds 2 --fixture)."""
    with gzip.open(PROGRAM, "rt") as f:
        program = json.load(f)
    return pt.ProgramTrace.read(str(RECORDED), program, n_devices=1)


def test_recorded_trace_gives_each_new_metric_of_the_cell(recorded):
    assert len(recorded.rounds()) == 2
    values = {n: f(recorded) for n, f in pt.METRICS.items()}
    assert values.pop("codec_ms") is None        # no codec in this cell
    assert values["host_syncs_per_round"] == 3.0
    assert all(v is not None and v > 0 for v in values.values()), values
    per_round_ms = recorded.red.window_s * 1e3 / 2
    assert values["aggregate_ms"] < values["local_train_ms"] < per_round_ms
    idle_ms = (recorded.red.window_s - recorded.red.busy_s) * 1e3 / 2
    assert values["feed_idle_ms"] < idle_ms


def test_recorded_trace_stages_clocks_and_idle_names(recorded):
    assert recorded.unstaged_share() < 0.05
    assert {s for st in recorded.stages for s in st} >= {
        "select", "local_train", "aggregate", "finalize"}
    leads = recorded.leads()
    assert list(leads) == ["round_step"] and len(leads["round_step"]) == 2
    assert all(lead > 0 for _, lead in leads["round_step"])
    idle = sum(b - a for p in recorded.idle_pieces() for a, b, _ in p)
    assert idle * 1e-9 == pytest.approx(
        recorded.red.window_s - recorded.red.busy_s, rel=1e-6)
    assert recorded.named_idle_share() >= 0.9
    # the breakdown now names gaps by the program's spans as well
    names = [n for n, _ in recorded.red.breakdown()["idle_gaps"]]
    assert all(n.startswith(("bench.", "fl.")) or n == "outside bench spans"
               for n in names)
    assert any(n.startswith("fl.") for n in names)
