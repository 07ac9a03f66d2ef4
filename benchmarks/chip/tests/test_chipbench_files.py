"""The chip benchmark's files: every cell loads from them, a new cell,
configuration or metric is picked up from new files alone, the work
counts match independent counts, and the entry point refuses a CPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness, work  # noqa: E402
from benchmarks.chip.peaks import PEAKS, UnknownDeviceError, peaks_for  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CHIP = ROOT / "benchmarks" / "chip"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1].startswith("benchmarks/chip/")
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= {w["name"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_from_its_files(workload):
    cell = harness.Cell.load(BENCH, workload)
    w = harness.find_workload(BENCH, workload)
    assert cell.cfg["name"] == w["config"]
    assert cell.chips == w["chips"] in (1, 4)
    assert cell.limits["sel_rows"] == 0
    assert cell.limits["feed_rows"] == 0
    assert set(cell.limits) <= {"sel_rows", "feed_rows", "loss", "loss_r1",
                                "update", "change", "update_median",
                                "change_median", "update_weights"}
    assert all(isinstance(v, (int, float)) for v in cell.limits.values())
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(harness.load_metric(m["name"]).read)
    from repro.core import FLConfig
    FLConfig(**cell.traffic["fl"])          # the traffic is a valid config
    shapes = jax.eval_shape(lambda k: cell.model.init_params(cell.cfg, k),
                            jax.random.PRNGKey(0))
    assert sorted(shapes) == sorted(cell.model.units(cell.cfg))
    assert len(cell.model.layer_macs(cell.cfg)) == len(shapes)


def test_a_cell_config_and_metric_added_as_files_alone(tmp_path):
    """A later change adds files and BENCHMARK.json entries and edits no
    file the harness has."""
    base = tmp_path / "chip"
    shutil.copytree(CHIP, base, ignore=shutil.ignore_patterns("tests"))
    bench = json.loads(json.dumps(BENCH))
    shutil.copy(base / "configs" / "vgg16-cifar10.json",
                base / "configs" / "vgg16-narrow.json")
    shutil.copy(base / "configs" / "vgg16-cifar10.py",
                base / "configs" / "vgg16-narrow.py")
    tr = json.loads((base / "traffic" / "sync-t25.json").read_text())
    tr["fl"]["train_fraction"] = 0.5
    (base / "traffic" / "sync-t50.json").write_text(json.dumps(tr))
    (base / "cells" / "vgg16-narrow.sync-t50.json").write_text(
        json.dumps({"limits": {"sel_rows": 0, "loss": 1, "update": 1,
                               "change": 1}}))
    (base / "metrics" / "rounds_traced.py").write_text(
        "def read(ctx):\n    return float(len(ctx.sels))\n")
    bench["configs"].append({"name": "vgg16-narrow"})
    bench["workloads"].append({"name": "vgg16-narrow.sync-t50",
                               "config": "vgg16-narrow",
                               "traffic": "sync-t50", "chips": 1})
    bench["per_layer"].append({"name": "rounds_traced", "unit": "1",
                               "workloads": ["vgg16-narrow.sync-t50"]})
    cell = harness.Cell.load(bench, "vgg16-narrow.sync-t50", base=base)
    assert cell.traffic["fl"]["train_fraction"] == 0.5
    assert [m["name"] for m in cell.per_layer] == ["rounds_traced"]
    metric = harness.load_metric("rounds_traced", base=base)
    ctx = harness.MetricContext(None, 1, {}, [np.ones((2, 3))], [], [], 1,
                                "none")
    assert metric.read(ctx) == 1.0


def _jaxpr_flops(fn, *args) -> float:
    """2 x multiply-accumulates of every convolution and matmul in the
    traced forward pass, read from the jaxpr's shapes."""
    total = 0.0
    for eqn in jax.make_jaxpr(fn)(*args).eqns:
        if eqn.primitive.name == "conv_general_dilated":
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            out = eqn.outvars[0].aval.shape
            dn = eqn.params["dimension_numbers"]
            kernel = [rhs[i] for i in dn.rhs_spec[2:]]
            cin = rhs[dn.rhs_spec[1]]
            total += 2 * np.prod(out) * np.prod(kernel) * cin
        elif eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            out = eqn.outvars[0].aval.shape
            total += 2 * np.prod(out) * np.prod([lhs[i] for i in lc])
    return float(total)


@pytest.mark.parametrize("config", ["vgg16-cifar10", "imdb-cnnlstm"])
def test_forward_flops_match_a_count_from_the_traced_shapes(config):
    cfg = json.loads((CHIP / "configs" / f"{config}.json").read_text())
    mod = harness.load_module(CHIP / "configs" / f"{config}.py", "cfgmod")
    params = jax.eval_shape(lambda k: mod.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    if config == "vgg16-cifar10":
        x = jax.ShapeDtypeStruct((2, *cfg["image"]), jnp.float32)
    else:
        x = jax.ShapeDtypeStruct((2, cfg["seq_len"]), jnp.int32)
    counted = _jaxpr_flops(lambda p, x: mod.forward(cfg, p, x), params, x)
    forward_only = work.step_flops(mod.layer_macs(cfg),
                                   np.zeros(len(params)))
    assert counted == 2 * forward_only


def test_vgg16_sizes_are_the_published_ones():
    cfg = json.loads((CHIP / "configs" / "vgg16-cifar10.json").read_text())
    mod = harness.load_module(CHIP / "configs" / "vgg16-cifar10.py", "vgg")
    params = jax.eval_shape(lambda k: mod.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    assert n == cfg["params_total"] == 14736714
    # per 32x32 image, by hand: H x W x 3 x 3 x C_in x C_out per conv
    by_hand = [32 * 32 * 9 * 3 * 64, 32 * 32 * 9 * 64 * 64,
               16 * 16 * 9 * 64 * 128, 16 * 16 * 9 * 128 * 128,
               8 * 8 * 9 * 128 * 256] + [8 * 8 * 9 * 256 * 256] * 2 + \
        [4 * 4 * 9 * 256 * 512] + [4 * 4 * 9 * 512 * 512] * 2 + \
        [2 * 2 * 9 * 512 * 512] * 3 + [512 * 10]
    assert mod.layer_macs(cfg) == by_hand
    assert sum(by_hand) == 313201664


def test_step_flops_follow_the_backward_pass():
    macs = [10, 20, 30]
    assert work.step_flops(macs, [0, 0, 0]) == 2 * 60
    # top unit trained: its weight gradient, no input gradient
    assert work.step_flops(macs, [0, 0, 1]) == 2 * 60 + 2 * 30
    # bottom unit trained: every weight-free input gradient above it
    assert work.step_flops(macs, [1, 0, 0]) == 2 * 60 + 2 * 10 + 2 * 50
    assert work.round_flops(macs, [[0, 0, 1], [0, 0, 0]], 3) == \
        3 * (180 + 120)


def test_byte_counts_match_hand_worked_sizes():
    leaves = [[10, 2], [5]]
    sel = [[1, 0], [1, 1]]
    # unit 0: 2 deltas + old + new of 12 floats; unit 1: 1 + 2 of 5
    assert work.reduce_bytes(leaves, sel) == 4 * 12 * 4 + 4 * 5 * 3
    # read 4 B + write 1 B per element, one 4 B scale per leaf row
    assert work.encode_bytes(leaves, sel) == \
        2 * (10 * 5 + 4 + 2 * 5 + 4) + (5 * 5 + 4)
    assert work.encode_bytes(leaves, sel, 0.5) == \
        2 * (10 * 4.5 + 4 + 2 * 4.5 + 4) + (5 * 4.5 + 4)
    assert work.uplink_bytes(leaves, sel, "none") == 2 * 48 + 20
    assert work.uplink_bytes(leaves, sel, "qint8") == 2 * (14 + 6) + 9
    assert work.reduce_bytes(leaves, [[0, 0], [0, 0]]) == 0


@pytest.mark.parametrize("codec", ["none", "qint8"])
def test_uplink_bytes_match_what_the_program_bills(codec):
    from repro.core import FLConfig, Federation, ModelSpec
    from repro.data import FederatedLoader
    cfg = json.loads((CHIP / "configs" / "imdb-cnnlstm.json").read_text())
    cfg.update(vocab=600, embed=8, conv_filters=4, lstm=3)
    mod = harness.load_module(CHIP / "configs" / "imdb-cnnlstm.py", "imdb")
    params = mod.init_params(cfg, jax.random.PRNGKey(1))
    loss = mod.loss(cfg)
    spec = ModelSpec("imdb", lambda k: params,
                     lambda p, b: (loss(p, b), {}), mod.units(cfg))
    loader = FederatedLoader(mod.make_data(cfg, 4, 3), batch_size=2,
                             steps_per_round=1)
    fl = FLConfig(n_clients=4, train_fraction=0.5, packed=True, codec=codec,
                  lr=3e-3)
    fed = Federation.from_config(spec, fl, data=loader, seed=2)
    fed.fit(2)
    leaves = work.unit_leaves(jax.eval_shape(lambda: params),
                              mod.units(cfg))
    ours = sum(work.uplink_bytes(leaves, s, codec)
               for s in fed.server.sel_history)
    assert ours == fed.comm_summary()["total_uplink_bytes"]


@pytest.mark.parametrize("stats, held", [
    ({"peak_bytes_in_use": 100, "peak_bytes_reserved": 40,
      "bytes_reserved": 30}, 140),
    ({"peak_bytes_in_use": 100, "bytes_reserved": 30}, 130),
    ({"peak_bytes_in_use": 100}, 100),
    ({}, 0)])
def test_held_bytes_count_the_programs_reserved_scratch(stats, held):
    assert harness.held_bytes(stats) == held


def test_update_weights_is_the_worst_kernel_or_matrix():
    from benchmarks.chip import compare
    p0 = {"w": np.ones((2, 2)), "b": np.ones(2), "k": np.ones((1, 1, 2))}
    ref = {"p0": p0, "losses": [np.ones(2)], "feed_rows": 0,
           "grad0_norms": {"w": 1.0, "b": 1.0, "k": 1.0},
           "p1": {"w": 3 * p0["w"], "b": 3 * p0["b"], "k": 3 * p0["k"]}}
    ref["p_last"] = ref["p1"]
    # update norms: reference w 4, b 2.83, k 2.83; the bias is off by half
    obs = dict(ref, p1={"w": 3 * p0["w"], "b": 2 * p0["b"],
                        "k": 2.5 * p0["k"]})
    r = compare.readings(obs, ref, [], 0)
    assert r["update"] == pytest.approx(0.5)
    assert r["update_weights"] == pytest.approx(0.25)
    assert r["feed_rows"] == 0


def test_peaks_table_is_keyed_by_device_kind():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert all(row["source"] for row in PEAKS.values())
    with pytest.raises(UnknownDeviceError):
        peaks_for("cpu")


@pytest.mark.parametrize("where", ["checkout", "benchmark_alone"])
def test_entry_point_refuses_a_cpu(where, tmp_path):
    """No TPU, or nothing beside the benchmark: non-zero, no result."""
    root = ROOT
    if where == "benchmark_alone":
        root = tmp_path / "alone"
        shutil.copytree(CHIP, root / "benchmarks" / "chip",
                        ignore=shutil.ignore_patterns("tests"))
        shutil.copy(ROOT / "BENCHMARK.json", root)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3000000001",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
