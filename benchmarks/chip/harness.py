"""One run of one benchmark cell, driven by the files named in
``BENCHMARK.json``.

A cell is a configuration (``configs/<name>.json`` with its sizes, and
``configs/<name>.py`` beside it with the benchmark's own weights, data,
plain reference and per-unit multiply-accumulates) under a traffic mix
(``traffic/<name>.json``: the federation's settings, local steps and
batch, the rounds billed and traced).  The limits of the comparison that
decides ``correct`` are in ``cells/<workload>.json``, and each per-layer
metric is read by ``metrics/<name>.py``.  Nothing here names a cell, a
configuration or a metric.

A run:

1. set-up: data and weights from ``--seed`` (weights in one jitted call
   on the device), the ``Federation`` with the traffic's settings, and
   three rounds through ``Federation.fit`` that compile the cell's round
   programs and record what the comparison needs: each client's loss,
   the weights after rounds 1 and 3, the selection rows and the rows fed;
2. the window: ``fit(1)`` calls, each ended by ``block_until_ready`` on
   the server state, for ``--seconds`` (or, traced, for the traffic's
   ``trace_rounds`` under the profiler);
3. the device's peak memory, then the program's state freed;
4. the plain reference over the same three rounds, on rows it draws
   from its own copy of the data (made again from the seed) at the
   places the program's loader fed, and the comparison.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.chip import compare, reference, work
from benchmarks.chip import trace as trace_mod
from benchmarks.chip.peaks import peaks_for

HERE = Path(__file__).resolve().parent
CHECK_ROUNDS = 3


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the files, found by name


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


@dataclasses.dataclass
class Cell:
    """Everything one workload's run reads from the benchmark's files."""
    name: str
    chips: int
    cfg: dict
    model: Any                 # configs/<config>.py
    traffic: dict
    limits: dict
    per_layer: List[dict]      # BENCHMARK.json entries this cell reports

    @classmethod
    def load(cls, bench: dict, name: str, base: Path = HERE) -> "Cell":
        w = find_workload(bench, name)
        cfg = load_json(base / "configs" / f"{w['config']}.json")
        model = load_module(base / "configs" / f"{w['config']}.py",
                            f"chipbench_config_{len(sys.modules)}")
        traffic = load_json(base / "traffic" / f"{w['traffic']}.json")
        limits = load_json(base / "cells" / f"{name}.json")["limits"]
        per_layer = [m for m in bench["per_layer"]
                     if name in m.get("workloads", [name])]
        return cls(name, w["chips"], cfg, model, traffic, limits, per_layer)


def load_metric(name: str, base: Path = HERE):
    return load_module(base / "metrics" / f"{name}.py",
                       f"chipbench_metric_{name.replace('.', '_')}")


def resolve(ref: str):
    """``"package.module:attr"`` -> the object."""
    mod, attr = ref.split(":")
    return getattr(importlib.import_module(mod), attr)


# ---------------------------------------------------------------------------
# the device


def check_devices(chips: int, require_chip: bool = True):
    import jax
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def held_bytes(stats: Dict[str, Any]) -> int:
    """What a device held at its peak: the buffers in use plus the
    scratch the runtime reserves for the compiled programs (their
    temporaries, which ``peak_bytes_in_use`` leaves out)."""
    reserved = stats.get("peak_bytes_reserved", stats.get("bytes_reserved",
                                                          0))
    return int(stats.get("peak_bytes_in_use", 0)) + int(reserved)


def peak_bytes(devices) -> int:
    """The most any of ``devices`` held (``held_bytes``)."""
    return max(held_bytes(d.memory_stats() or {}) for d in devices)


class GcPauses:
    """The pauses of Python's garbage collector while registered."""

    def __init__(self):
        self.t0: Optional[float] = None
        self.pauses: List[float] = []

    def __call__(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
        elif self.t0 is not None:
            self.pauses.append(time.perf_counter() - self.t0)
            self.t0 = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


class CompileCounter:
    """Counts the programs JAX traces and compiles while ``active``."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.active = False
        self.counts = {e: 0 for e in self.EVENTS}

    def __call__(self, event, duration, **kw):
        if self.active and event in self.counts:
            self.counts[event] += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


# ---------------------------------------------------------------------------
# set-up


def seeds_from(seed: int) -> Dict[str, int]:
    """Independent data and weight seeds from ``--seed`` (any size)."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return {"data": int(words[0]), "weights": int(words[1])}


def n_train(n_units: int, fraction: float) -> int:
    """The paper's 25/50/75 % settings as unit counts."""
    return max(1, round(n_units * fraction))


def make_loader_class():
    from repro.data import FederatedLoader

    class FeedRecorder(FederatedLoader):
        """The program's loader, recording every chunk it serves while
        ``recording`` (the set-up rounds)."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.recording = True
            self.fed: List[tuple] = []      # (round, client ids, batches)

        def client_batches(self, rnd, client_ids):
            out = super().client_batches(rnd, client_ids)
            if self.recording:
                self.fed.append((rnd, np.asarray(client_ids), out))
            return out

    return FeedRecorder


def make_hook_class():
    from repro.core import ServerHook

    class LossRecorder(ServerHook):
        """Each client's round loss, as the round step returns it."""

        def __init__(self):
            self.losses: List[np.ndarray] = []

        def on_round_end(self, server, record, metrics):
            self.losses.append(np.asarray(metrics["loss_per_client"],
                                          np.float64))

    return LossRecorder


@dataclasses.dataclass
class Setup:
    fed: Any
    units: List[str]
    shapes: Any
    n_train: int
    observed: Dict[str, Any]    # program readings of the set-up rounds
    feed: List[Dict[str, Any]]  # the selections and the rows fed


def build(cell: Cell, seed: int) -> Setup:
    """Data, weights and the Federation; then the set-up rounds."""
    import jax
    from repro.core import FLConfig, Federation, ModelSpec

    cfg, tr = cell.cfg, cell.traffic
    fl = FLConfig(**tr["fl"])
    s = seeds_from(seed)
    n_data = fl.n_registered or fl.n_clients
    data = cell.model.make_data(cfg, n_data, s["data"])
    loader = make_loader_class()(data, batch_size=tr["batch_size"],
                                 steps_per_round=tr["local_steps"],
                                 key=s["data"])
    key = jax.random.PRNGKey(s["weights"])
    params = jax.jit(lambda k: cell.model.init_params(cfg, k))(key)
    shapes = jax.eval_shape(lambda: params)
    units = cell.model.units(cfg)

    prog = cfg["program"]
    apply, xent = resolve(prog["apply"]), resolve(prog["loss"])

    def loss_fn(p, batch):
        return xent(apply(p, batch[prog["inputs"]]), batch[prog["labels"]]), {}

    spec = ModelSpec(name=cfg["name"], init_params=lambda _key: params,
                     loss_fn=loss_fn, unit_order=units)
    hook = make_hook_class()()
    fed = Federation.from_config(spec, fl, data=loader,
                                 seed=tr["program_seed"], hooks=[hook])
    del params

    host = reference.host
    observed = {"losses": [], "times": []}
    for r in range(CHECK_ROUNDS):
        t0 = time.perf_counter()
        fed.fit(1)
        jax.block_until_ready(fed.state)
        observed["times"].append(time.perf_counter() - t0)
        if r == 0:
            observed["p1"] = host(fed.params)
    observed["p_last"] = host(fed.params)
    observed["losses"] = list(hook.losses)
    fed.server.hooks.remove(hook)
    loader.recording = False

    feed = []
    for r in range(CHECK_ROUNDS):
        chunks = [(ids, b) for rr, ids, b in loader.fed if rr == r]
        feed.append({"sel": np.asarray(fed.server.sel_history[r]),
                     "ids": [i for i, _ in chunks],
                     "chunks": [b for _, b in chunks]})
    loader.fed = []
    return Setup(fed, units, shapes,
                 n_train(len(units), fl.train_fraction), observed, feed)


# ---------------------------------------------------------------------------
# the window


def window(st: Setup, seconds: float, rounds: Optional[int] = None,
           spans: Optional[list] = None) -> Dict[str, Any]:
    """``fit(1)`` calls until ``seconds`` have passed (or ``rounds`` are
    done); each round's wall time, and the rounds that raised or gave a
    non-finite loss.  ``spans``, if given, collects ``(start, end, name)``
    on the wall clock (ns) of each ``bench.fit`` (the ``fit(1)`` call)
    and ``bench.sync`` (the wait for the server state)."""
    import jax
    fed = st.fed
    first = len(fed.history)
    times, failed, attempted = [], 0, 0
    t_start = time.perf_counter()
    t_end = t_start
    while True:
        attempted += 1
        t0 = time.perf_counter()
        try:
            if spans is None:
                fed.fit(1)
                jax.block_until_ready(fed.state)
            else:
                a = time.time_ns()
                fed.fit(1)
                b = time.time_ns()
                jax.block_until_ready(fed.state)
                spans += [(a, b, "bench.fit"), (b, time.time_ns(),
                                                 "bench.sync")]
        except Exception:
            traceback.print_exc()
            failed += 1
            t_end = time.perf_counter()
            break
        t_end = time.perf_counter()
        times.append(t_end - t0)
        if not math.isfinite(fed.history[-1].loss):
            failed += 1
        if rounds is not None and len(times) >= rounds:
            break
        if rounds is None and t_end - t_start >= seconds:
            break
    sels = [np.asarray(s) for s in fed.server.sel_history[first:]]
    return {"times": times, "attempted": attempted, "failed": failed,
            "window_s": t_end - t_start, "sels": sels}


def p95(values: List[float]) -> float:
    """The 95th percentile, by ``statistics.quantiles`` (exclusive)."""
    if len(values) < 2:
        return float(values[0]) if values else float("nan")
    return float(statistics.quantiles(values, n=20)[-1])


# ---------------------------------------------------------------------------
# a whole run


def reference_readings(cell: Cell, seed: int, feed, *, dtype=None,
                       precision: Optional[str] = "highest",
                       fault: Optional[str] = None) -> Dict[str, Any]:
    """The plain reference over the recorded set-up rounds (float32 at
    "highest" precision unless told otherwise)."""
    import jax
    import jax.numpy as jnp
    cfg = cell.cfg
    rfeed, bad = reference_feed(cell, seed, feed)
    key = jax.random.PRNGKey(seeds_from(seed)["weights"])
    p0 = jax.jit(lambda k: cell.model.init_params(cfg, k))(key)
    out = reference.run_rounds(
        cell.model.loss(cfg), p0, rfeed, cell.model.units(cfg),
        cell.traffic["fl"]["lr"], chunk=cfg["reference_chunk"],
        dtype=jnp.float32 if dtype is None else dtype, precision=precision,
        fault=fault, shards=cell.traffic["fl"].get("client_shards") or 1)
    out["p0"] = reference.host(p0)
    out["feed_rows"] = bad
    return out


def reference_feed(cell: Cell, seed: int, feed):
    """The rounds as the reference follows them, and the count of rows
    the program's loader fed wrongly.

    The data is made again from the seed, not taken from the program.
    Each row fed to a client is looked up among that client's own rows
    (all its fields: inputs and labels); the reference gets the rows it
    finds, in the order fed, and each client's share of the average from
    its own row count.  Counted as wrong: a row that is not among its
    client's rows with those fields (another client's row, a label out
    of line with its input), and a row fed twice in one client's round
    where the client holds enough rows for a round without repeats.
    """
    cfg, tr = cell.cfg, cell.traffic
    fl = tr["fl"]
    pool = cell.model.make_data(cfg, fl.get("n_registered") or
                                fl["n_clients"], seeds_from(seed)["data"])
    key = cfg["program"]["inputs"]
    need = tr["local_steps"] * tr["batch_size"]
    lookup: Dict[int, Dict[bytes, List[int]]] = {}
    bad, out = 0, []
    for rnd in feed:
        chunks, sizes = [], []
        for ids, fed in zip(rnd["ids"], rnd["chunks"]):
            rows = []
            for j, cid in enumerate(int(c) for c in ids):
                own = pool[cid]
                if cid not in lookup:
                    lookup[cid] = {}
                    for i, row in enumerate(own[key]):
                        lookup[cid].setdefault(row.tobytes(), []).append(i)
                got = {k: np.asarray(v[j]).reshape((need,) + v.shape[3:])
                       for k, v in fed.items()}
                idx = np.asarray([next(
                    (i for i in lookup[cid].get(got[key][r].tobytes(), [])
                     if all(np.array_equal(own[k][i], got[k][r])
                            for k in got)), -1) for r in range(need)])
                found = idx[idx >= 0]
                bad += int(np.sum(idx < 0))
                if len(own[key]) >= need:
                    bad += len(found) - len(np.unique(found))
                rows.append(np.maximum(idx, 0))
                sizes.append(len(own[key]))
            picked = [pool[int(c)] for c in ids]
            chunks.append({k: np.stack([p[k][i] for p, i in zip(picked, rows)])
                           .reshape((len(rows), tr["local_steps"],
                                     tr["batch_size"]) + pool[0][k].shape[1:])
                           for k in pool[0]})
        out.append({"sel": rnd["sel"], "chunks": chunks,
                    "weights": np.asarray(sizes, np.float32)})
    return out, bad


def run(cell: Cell, *, seed: int, seconds: float, traced: bool,
        t_start: float, require_chip: bool = True) -> Dict[str, Any]:
    """One run; returns the result object (its last line)."""
    import jax
    from repro.launch.env import enable_compile_cache

    devices = check_devices(cell.chips, require_chip)
    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x {len(jax.devices())}, "
        f"jax {jax.__version__}, compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    st = build(cell, seed)
    log(f"set-up rounds (s): {st.observed['times']}")
    tr = cell.traffic
    with CompileCounter() as cc:
        setup_s = time.perf_counter() - t_start
        cc.active = True
        if traced:
            tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
            spans: list = []
            # device events only: host tracing records every tile of the
            # feed's host-to-device transpose and slows a round 4x
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                # one round first, outside the window: the profiler's
                # start stalls the first round it sees
                window(st, seconds, rounds=1)
                win = window(st, seconds, rounds=tr["trace_rounds"],
                             spans=spans)
            finally:
                jax.profiler.stop_trace()
        else:
            with GcPauses() as gcp:
                win = window(st, seconds)
        cc.active = False
    log(f"window: {len(win['times'])} rounds in {win['window_s']!r} s; "
        f"programs traced in the window "
        f"{cc.counts[CompileCounter.EVENTS[0]]}, compiled "
        f"{cc.counts[CompileCounter.EVENTS[1]]}")
    mem = peak_bytes(devices)
    log(f"memory_stats of {dev}: {dev.memory_stats()}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}

    fl = tr["fl"]
    leaves = work.unit_leaves(st.shapes, st.units)
    result: Dict[str, Any] = {"correct": False,
                              "attempted": win["attempted"],
                              "failed": win["failed"]}
    metrics: Dict[str, Any] = {}
    if traced:
        red = trace_mod.reduce(trace_mod.find_xplane(tdir), spans,
                               n_devices=len(devices))
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = MetricContext(
            trace=red, chips=len(devices),
            peaks=peaks_for(dev.device_kind), sels=win["sels"],
            macs=cell.model.layer_macs(cell.cfg), unit_leaves=leaves,
            examples_per_client=tr["local_steps"] * tr["batch_size"],
            codec=fl.get("codec", "none"))
        for m in cell.per_layer:
            value = load_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    else:
        k = tr["uplink_rounds"]
        up = sum(work.uplink_bytes(leaves, s, fl.get("codec", "none"))
                 for s in win["sels"][:k]) / max(1, min(k, len(win["sels"])))
        ms = [t * 1e3 for t in win["times"]]
        metrics = {
            "round_ms": {"value": win["window_s"] * 1e3 / max(1, len(ms)),
                         "unit": "ms"},
            "round_ms_p95": {"value": p95(ms), "unit": "ms"},
            "uplink_mb_per_round": {"value": up / 1e6, "unit": "MB"},
            "peak_hbm_mb": {"value": mem / 1e6, "unit": "MB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        med = statistics.median(ms) if ms else 0.0
        slow = sorted(range(len(ms)), key=ms.__getitem__)[-3:][::-1]
        log(f"rounds in window {len(ms)}: median {med!r} ms, "
            f"{sum(t > 1.5 * med for t in ms)} over 1.5x the median, the "
            f"longest (round: ms) {[(i, ms[i]) for i in slow]}; garbage "
            f"collections {len(gcp.pauses)}, {sum(gcp.pauses)!r} s in all, "
            f"longest {max(gcp.pauses, default=0.0)!r} s; uplink over the "
            f"first {min(k, len(win['sels']))}")

    obs = st.observed
    feed = st.feed
    n_tr = st.n_train
    del st
    gc.collect()
    ref = reference_readings(cell, seed, feed)
    checks = compare.checks(obs, ref, feed, n_tr, cell.limits)
    result["correct"] = win["failed"] == 0 and compare.passed(checks)
    result["metrics"] = metrics
    result["device"] = device
    for line in compare.lines(checks):
        log(line)
    result["checks"] = checks
    return result


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader is given."""
    trace: Any                 # trace.Reduced
    chips: int
    peaks: dict
    sels: List[np.ndarray]     # the traced rounds' selection matrices
    macs: List[int]
    unit_leaves: List[List[int]]
    examples_per_client: int   # local steps x batch
    codec: str
