"""The reduction from a profiler trace to the per-layer metrics, on
intervals built by hand and on a two-round trace of the
vgg16-cifar10.sync-t25 cell recorded on a TPU v5e."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness, trace  # noqa: E402
from benchmarks.chip.peaks import peaks_for  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "vgg16-cifar10.sync-t25.xplane.pb.gz"
SPANS = DATA / "vgg16-cifar10.sync-t25.spans.json"


def _ops(*events):
    return trace.Ops(np.asarray([s for s, _, _ in events], np.int64),
                     np.asarray([e for _, e, _ in events], np.int64),
                     [n for _, _, n in events])


def test_busy_is_the_union_and_gaps_are_named_by_span():
    dev = _ops((10, 30, "%fusion.1 = f32[8]"), (20, 40, "%while.2 = (s32[])"),
               (60, 70, "%fusion.3 = f32[8]"))
    spans = [(0, 100, "bench.fit"), (50, 58, "bench.sync")]
    red = trace.Reduced(0, 100, [dev], spans)
    assert red.busy_ns(dev) == 40
    assert red.busy_s == pytest.approx(40e-9)
    assert red.window_s == pytest.approx(100e-9)
    gaps = red.gaps()
    assert [round(s * 1e9) for _, s in gaps] == [30, 20, 10]
    named = {round(s * 1e9): n for n, s in gaps}
    assert named == {30: "bench.fit", 20: "bench.sync", 10: "bench.fit"}
    assert red.op_seconds(trace.matcher([r"^%fusion"])) == \
        pytest.approx(30e-9)


def test_collective_time_counts_only_what_nothing_else_overlaps():
    dev = _ops((0, 10, "%all-gather.1 = f32[4,8]"), (5, 8, "%fusion.2 = f32"),
               (20, 30, "%all-reduce-start.3 = f32"), (40, 50, "%fusion.4"))
    red = trace.Reduced(0, 60, [dev, _ops((0, 5, "%fusion.9"))], [])
    assert [round(x * 1e9) for x in red.exposed_collective_s()] == [17, 0]


@pytest.fixture(scope="module")
def recorded():
    spans = [tuple(s) for s in json.loads(SPANS.read_text())]
    return trace.reduce(str(RECORDED), spans, n_devices=1)


def test_recorded_trace_has_one_chip_and_the_harness_spans(recorded):
    assert len(recorded.devices) == 1
    assert {n for _, _, n in recorded.spans} == {"bench.fit", "bench.sync"}
    assert 0 < recorded.busy_s < recorded.window_s
    idle = sum(s for _, s in recorded.gaps())
    assert idle == pytest.approx(recorded.window_s - recorded.busy_s,
                                 rel=1e-6)
    assert recorded.exposed_collective_s() == [0.0]


def test_recorded_trace_breakdown_names_ops_and_gaps(recorded):
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and len(n) < 100 and s > 0
               for n, s in b["device_ops"])
    assert all(n.startswith("bench.") or n == "outside bench spans"
               for n, _ in b["idle_gaps"])


def test_recorded_trace_gives_each_metric_of_the_cell(recorded):
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.Cell.load(bench, "vgg16-cifar10.sync-t25")
    import jax
    shapes = jax.eval_shape(lambda k: cell.model.init_params(cell.cfg, k),
                            jax.random.PRNGKey(0))
    units = cell.model.units(cell.cfg)
    from benchmarks.chip import work
    # two rounds of 8 clients training 4 of 14 units
    sel = np.zeros((8, 14))
    sel[:, [0, 5, 9, 13]] = 1
    ctx = harness.MetricContext(
        trace=recorded, chips=1, peaks=peaks_for("TPU v5 lite"),
        sels=[sel, sel], macs=cell.model.layer_macs(cell.cfg),
        unit_leaves=work.unit_leaves(shapes, units),
        examples_per_client=cell.traffic["local_steps"]
        * cell.traffic["batch_size"], codec="none")
    values = {m["name"]: harness.load_metric(m["name"]).read(ctx)
              for m in cell.per_layer}
    assert set(values) == {"device_idle_share", "round_mfu",
                           "masked_agg_roofline"}
    for name, v in values.items():
        assert v is not None and 0 < v < 100, (name, v)
    # the quantize-pack kernel does not run in this cell: no reading
    qp = harness.load_metric("quantize_pack_roofline")
    assert qp.read(ctx) is None
