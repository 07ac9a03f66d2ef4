"""The program's own spans, host-sync counter, stage maps and per-step
losses, on the benchmark's cells cut to a CPU's size."""
import collections
import re

import numpy as np
import pytest

import chipbench_tiny as tiny
from benchmarks.chip import harness
from repro.analysis.compileguard import hlo_stages
from repro.common import spans
from repro.core import ServerHook

STAGES = {"select", "slot_plan", "local_train", "codec", "faults", "gate",
          "aggregate", "finalize"}
# the host reads a round makes, counted from the code: Server.run_round
# reads the weights, the selection and the loss; the cohort engine the
# sampler's key, the weights, each chunk's losses, the selection, the
# round loss and the per-client losses for the fleet's EMAs
SYNCS = {"vgg16-cifar10.sync-t25": ["weights", "sel", "loss"],
         "imdb-cnnlstm.cohort256-t50-qint8":
             ["sample", "weights", "loss", "loss", "sel", "loss",
              "loss_per_client"]}
PROGRAMS = {"vgg16-cifar10.sync-t25": ["round_step"],
            "imdb-cnnlstm.cohort256-t50-qint8":
                ["cohort_select", "cohort_acc_init", "cohort_chunk",
                 "cohort_chunk", "cohort_finalize"]}


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    import repro.launch.env as env
    monkeypatch.setattr(env, "enable_compile_cache", lambda: "off")


@pytest.fixture(scope="module", params=sorted(SYNCS))
def recorded(request):
    """Two rounds of a tiny cell with the recorder on."""
    cell = tiny.tiny_cell(request.param)
    st = harness.build(cell, 11)
    spans.drain()
    spans.enable()
    try:
        recs = st.fed.fit(2)[-2:]
    finally:
        spans.disable()
    return request.param, st, recs, spans.drain()


def _children(rec):
    kids = collections.defaultdict(list)
    for s in rec.spans:
        kids[s.parent].append(s)
    for v in kids.values():
        v.sort(key=lambda s: s.start_ns)
    return kids


def test_each_round_is_one_span_holding_feed_dispatch_and_sync(recorded):
    workload, st, recs, rec = recorded
    kids = _children(rec)
    rounds = [s for s in kids[None]]
    assert [s.name for s in rounds] == ["fl.round", "fl.round"]
    assert [s.round for s in rounds] == [r.round for r in recs]
    by_id = {s.id: s for s in rec.spans}
    for rnd in rounds:
        inside = [s for s in rec.spans if s.round == rnd.round
                  and s is not rnd]
        # every span of the round lies in it, and its parent chain ends
        # at the round
        for s in inside:
            assert rnd.start_ns <= s.start_ns <= s.end_ns <= rnd.end_ns
            p = s
            while p.parent is not None:
                p = by_id[p.parent]
            assert p is rnd
        feeds = [s for s in inside if s.name == "fl.feed"]
        assert feeds
        for f in feeds:
            assert [c.name for c in kids[f.id]] == ["fl.feed.build",
                                                    "fl.feed.h2d"]
        assert [s.attrs["program"] for s in inside
                if s.name == "fl.dispatch"] == PROGRAMS[workload]
        assert [s.attrs["what"] for s in inside if s.name == "fl.sync"] \
            == SYNCS[workload]
        assert [s.attrs["phase"] for s in kids[rnd.id]
                if s.name == "fl.hooks"] == ["start", "end"]
        if workload.startswith("imdb"):
            chunks = [s for s in kids[rnd.id] if s.name == "fl.chunk"]
            assert [c.attrs["chunk"] for c in chunks] == [0, 1]
            for c in chunks:
                assert [k.name for k in kids[c.id]] == [
                    "fl.feed", "fl.dispatch", "fl.sync"]
        else:
            assert [k.name for k in kids[rnd.id]][:2] == ["fl.feed",
                                                          "fl.hooks"]


def test_host_syncs_count_the_reads_in_the_code(recorded):
    workload, _, recs, rec = recorded
    assert rec.counters == {(r.round, "host_syncs"): len(SYNCS[workload])
                            for r in recs}


def test_round_seconds_is_the_round_span(recorded):
    _, _, recs, rec = recorded
    rounds = {s.round: s for s in rec.spans if s.name == "fl.round"}
    for r in recs:
        s = rounds[r.round]
        assert r.seconds == pytest.approx((s.end_ns - s.start_ns) * 1e-9,
                                          rel=0, abs=1e-9)


def test_every_scoped_instruction_gets_a_stage(recorded):
    workload, _, _, rec = recorded
    names = {"vgg16-cifar10.sync-t25": ["round_step"],
             "imdb-cnnlstm.cohort256-t50-qint8": ["cohort_chunk"]}[workload]
    for name in names:
        guard = rec.programs[name]
        (args, kwargs), = guard._abstract.values()
        text = guard._jit.lower(*args, **kwargs).compile().as_text()
        module, direct = hlo_stages(text, inherit=False)
        _, stages = hlo_stages(text)
        assert guard.stage_maps() == {module: stages}
        scoped, every = set(), set()
        for line in text.splitlines():
            m = re.match(r'\s*(?:ROOT )?%?([\w.\-]+) = ', line)
            if m:
                every.add(m.group(1))
                if re.search(r'op_name="[^"]*\bfl/', line):
                    scoped.add(m.group(1))
        assert scoped and scoped == set(direct)
        # what the compiler added without a scope inherits one
        assert set(stages) == every
        assert all(stages[n] == st for n, st in direct.items())
        assert set(stages.values()) <= STAGES
        assert {"local_train", "aggregate"} <= set(stages.values())


def test_per_step_losses_stay_on_the_device(recorded):
    workload, st, recs, rec = recorded
    fed = st.fed
    # one more round, its metrics seen by a hook
    seen = {}

    class Grab(ServerHook):
        def on_round_end(self, server, record, metrics):
            seen.update(metrics)

    fed.server.hooks.append(Grab())
    try:
        fed.fit(1)
    finally:
        fed.server.hooks.pop()
    steps = st.fed.loader.steps
    c = fed.fl.n_clients
    assert seen["loss_steps"].shape == (c, steps)
    assert not isinstance(seen["loss_steps"], np.ndarray)
    np.testing.assert_allclose(np.asarray(seen["loss_steps"]).mean(axis=1),
                               np.asarray(seen["loss_per_client"]),
                               rtol=1e-6)
