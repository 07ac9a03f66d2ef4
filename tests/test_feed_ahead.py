"""The sync engine's one-round-ahead feed (DESIGN.md §13): while round r
runs, ``Federation.fit`` builds round r+1's batches and enqueues their
copy; round r+1 takes them from a one-slot holder.  A round's rows are
a pure function of (loader key, round, client), so a staged round and a
round built in place give bitwise the same state."""
import collections
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import spans
from repro.core import FLConfig, Federation
from repro.data import FederatedLoader
from repro.models.toy import init_toy_mlp, toy_loss, toy_units

ROOT = Path(__file__).resolve().parents[1]
C, D, OUT = 4, 8, 3


def _loader(n=12, seed=5):
    rng = np.random.default_rng(seed)
    data = [{"x": rng.standard_normal((n, D)).astype(np.float32),
             "y": rng.standard_normal((n, OUT)).astype(np.float32)}
            for _ in range(C)]
    return FederatedLoader(data, batch_size=2, steps_per_round=2, key=seed)


def _fed(loader=None, **fl_kw):
    params = init_toy_mlp(jax.random.PRNGKey(0), n_blocks=4, d=D,
                          hidden=16, out=OUT)
    fl = FLConfig(**dict(dict(n_clients=C, train_fraction=0.5,
                              fused_agg="off"), **fl_kw))
    return Federation(loss_fn=toy_loss, params=params,
                      assign=toy_units(params), fl=fl,
                      loader=loader if loader is not None else _loader(),
                      seed=3)


def _run_round(fed, r):
    """Round ``r`` by ``run_round``, on batches the loader builds afresh
    and with the weights ``fit`` passes."""
    return fed.run_round(fed.loader.round_batches(r),
                         weights=jnp.asarray(fed.loader.weights()))


def _fresh(fed, rounds):
    for r in range(rounds):
        _run_round(fed, r)
    return fed


def _assert_same_run(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a.state),
                    jax.tree_util.tree_leaves(b.state)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert [r.loss for r in a.history] == [r.loss for r in b.history]
    assert len(a.server.sel_history) == len(b.server.sel_history)
    for x, y in zip(a.server.sel_history, b.server.sel_history):
        assert np.array_equal(x, y)


def _recorded(fn):
    spans.drain()
    spans.enable()
    try:
        fn()
    finally:
        spans.disable()
    return spans.drain()


def _children(rec):
    kids = collections.defaultdict(list)
    for s in rec.spans:
        kids[s.parent].append(s)
    for v in kids.values():
        v.sort(key=lambda s: s.start_ns)
    return kids


@pytest.mark.parametrize("topology", ["hub", "hierarchical", "gossip"])
def test_fit_is_bitwise_run_round_on_batches_built_fresh(topology):
    fed = _fed(topology=topology)
    for _ in range(5):
        fed.fit(1)
    assert fed.feed_counts == {"staged": 4, "built": 1}
    # the slot holds the next round on the device, each leaf of more
    # than three dimensions as (clients, rows, last two merged)
    assert fed._staged[1] == 5
    _, shapes, sent = fed._staged[2]
    assert [x.shape for x in sent] == [
        (s[0], int(np.prod(s[1:-2])), s[-2] * s[-1]) for s in shapes]
    assert all(isinstance(x, jax.Array) for x in sent)
    _assert_same_run(fed, _fresh(_fed(topology=topology), 5))


def test_one_fit_of_many_rounds_stages_each_round():
    a, b = _fed(), _fed()
    a.fit(4)
    for _ in range(4):
        b.fit(1)
    assert a.feed_counts == b.feed_counts == {"staged": 3, "built": 1}
    _assert_same_run(a, b)


def test_after_run_round_the_next_fit_builds_in_place():
    fed = _fed()
    fed.fit(2)
    _run_round(fed, 2)
    assert fed._staged is None
    rec = _recorded(lambda: fed.fit(2))
    feeds = [s for s in sorted(rec.spans, key=lambda s: s.start_ns)
             if s.name == "fl.feed"]
    assert [f.attrs["staged"] for f in feeds] == [False, True]
    assert fed.feed_counts == {"staged": 2, "built": 2}
    _assert_same_run(fed, _fresh(_fed(), 5))


def test_after_restore_the_next_fit_builds_in_place(tmp_path):
    fed = _fed()
    fed.fit(3)
    fed.save(str(tmp_path / "ckpt"))
    fed.fit(2)
    fed.restore(str(tmp_path / "ckpt"))
    assert fed._staged is None and len(fed.history) == 3
    before = dict(fed.feed_counts)
    rec = _recorded(lambda: fed.fit(3))
    feeds = sorted((s for s in rec.spans if s.name == "fl.feed"),
                   key=lambda s: s.start_ns)
    assert [(f.round, f.attrs["staged"]) for f in feeds] == [
        (3, False), (4, True), (5, True)]
    assert fed.feed_counts["built"] == before["built"] + 1
    _assert_same_run(fed, _fresh(_fed(), 6))


def test_a_new_loader_is_built_in_place():
    fed = _fed()
    fed.fit(2)
    fed.loader = _loader(seed=9)
    fed.fit(2)
    assert fed.feed_counts == {"staged": 2, "built": 2}
    assert fed._staged[0] is fed.loader and fed._staged[1] == 4
    ref = _fed()
    ref.fit(2)
    ref.loader = _loader(seed=9)
    for r in (2, 3):
        _run_round(ref, r)
    _assert_same_run(fed, ref)


@pytest.mark.parametrize("mode", ["cohort", "async"])
def test_the_engines_stage_nothing(mode):
    kw = {"cohort": dict(packed=True, n_registered=C, cohort_chunk=2),
          "async": dict(packed=True, async_buffer=2,
                        client_delay_dist="exponential:1.0")}[mode]
    fed = _fed(**kw)
    rec = _recorded(lambda: fed.fit(2))
    assert fed._staged is None
    assert fed.feed_counts == {"staged": 0, "built": 0}
    kids = _children(rec)
    names = {s.name for s in rec.spans}
    assert "fl.feed.ahead" not in names
    feeds = [s for s in rec.spans if s.name == "fl.feed"]
    assert feeds
    for f in feeds:
        assert f.attrs == {}
        assert [k.name for k in kids[f.id]] == ["fl.feed.build",
                                                "fl.feed.h2d"]
    if mode == "cohort":
        for rnd in kids[None]:
            chunks = [s for s in kids[rnd.id] if s.name == "fl.chunk"]
            assert [c.attrs["chunk"] for c in chunks] == [0, 1]
            for c in chunks:
                assert [k.name for k in kids[c.id]] == [
                    "fl.feed", "fl.dispatch", "fl.sync"]


def test_the_sync_tiny_cell_stages_every_round_after_the_first():
    sys.path.insert(0, str(ROOT / "benchmarks" / "chip" / "tests"))
    import chipbench_tiny as tiny
    import repro.launch.env as env
    from benchmarks.chip import harness
    mp = pytest.MonkeyPatch()
    mp.setattr(env, "enable_compile_cache", lambda: "off")
    try:
        cell = tiny.tiny_cell("vgg16-cifar10.sync-t25")
        out = {}

        def run():
            # the set-up rounds are the fresh Federation's first three
            out["st"] = harness.build(cell, 2 ** 31 + 7)
            out["st"].fed.fit(2)
        rec = _recorded(run)
    finally:
        mp.undo()
    fed = out["st"].fed
    assert fed.feed_counts == {"staged": 4, "built": 1}
    kids = _children(rec)
    rounds = sorted(kids[None], key=lambda s: s.round)
    assert [r.name for r in rounds] == ["fl.round"] * 5
    for rnd in rounds:
        (feed,) = [s for s in kids[rnd.id] if s.name == "fl.feed"]
        assert feed.attrs == {"staged": rnd.round > 0}
        assert [k.name for k in kids[feed.id]] == ["fl.feed.build",
                                                   "fl.feed.h2d"]
        (ahead,) = [s for s in kids[rnd.id] if s.name == "fl.feed.ahead"]
        assert ahead.attrs == {"round": rnd.round + 1}
        assert [k.name for k in kids[ahead.id]] == ["fl.feed.build",
                                                    "fl.feed.h2d"]
        # staged after the dispatch and before the first read of its
        # results
        order = [s.name for s in kids[rnd.id]]
        assert order.index("fl.dispatch") < order.index("fl.feed.ahead")
        after = [s.attrs.get("what") for s in kids[rnd.id]
                 if s.start_ns > ahead.end_ns and s.name == "fl.sync"]
        assert after == ["sel", "loss"]
    assert rec.counters == {(r.round, "host_syncs"): 3 for r in rounds}


SHARDED = """
import json, sys
sys.path[:0] = [{tests!r}]
import jax, numpy as np
from jax.sharding import PartitionSpec as P
import test_feed_ahead as t
from repro.launch.mesh import client_split
fed = t._fed(client_shards=4)
for _ in range(5):
    fed.fit(1)
ref = t._fresh(t._fed(client_shards=4), 5)
t._assert_same_run(fed, ref)
split = client_split(4)
# the staged copy, and the batches the round step reads from it
sent = fed._staged[2]
leaves = sent[2] + jax.tree_util.tree_leaves(fed.server.unpack_batches(sent))
placed = [x.sharding.is_equivalent_to(split, x.ndim)
          and x.sharding.spec == P("client") for x in leaves]
homes = sorted((s.device.id, s.data.shape[0])
               for x in leaves for s in x.addressable_shards)
# a caller-built round is placed alike: still one program
t._run_round(fed, 5)
fed.fit(1)
print(json.dumps({{"placed": placed, "homes": homes,
                  "programs": fed.server.round_step.cache_size,
                  "jit_programs": fed.server.round_step._jit._cache_size(),
                  "counts": fed.feed_counts}}))
"""


def test_client_shards_stage_on_the_client_split_bitwise():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-c",
         SHARDED.format(tests=str(Path(__file__).resolve().parent))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["placed"] == [True] * 4
    assert res["homes"] == [[d, 1] for d in range(4) for _ in range(4)]
    assert res["programs"] == res["jit_programs"] == 1
    assert res["counts"] == {"staged": 4, "built": 2}
