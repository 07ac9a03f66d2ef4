"""Trace one cell of the chip benchmark with the program's span recorder
on, and print what its spans, counters and stage maps give.

    python3 benchmarks/chip/trace_program.py --workload <cell> \\
        --seed <n> [--rounds 20] [--cost-windows 3 --cost-seconds 10] \\
        [--fixture <dir>] [--out <file>]

Run from the root of a checkout, on the chip.  Set-up is the
benchmark's (``harness.build``: data, weights, the Federation, three
set-up rounds).  Then, with ``--cost-windows K``, K untraced windows of
``--cost-seconds`` with the recorder off and K with it on, alternating,
each giving ``round_ms`` as the benchmark computes it: the cost of the
recorder when on.  Then ``--rounds`` rounds under the profiler with the
harness's options (device events only), after one round under it
outside the window, as ``harness.run`` traces, with the recorder on.

The result line holds the program's per-layer readings
(``program_trace.METRICS``), the cell's accepted per-layer metrics read
from the same trace, each stage's device time, idle time by the span
that holds it, and three checks: the share of busy device time with no
stage (and with none by the scopes' metadata alone, before the
compiler's own instructions inherit one), the leads of a program's
first device operation over the ``fl.dispatch`` span that launched it,
and the share of idle time inside ``bench.fit`` that the program's spans
name.  ``--fixture`` writes the trace and the program side
(``program_trace.records_to_json``) gzipped into a directory.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import statistics
import sys
import tempfile
from pathlib import Path
from typing import Any, Dict, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def profile_options():
    """The harness's: device events only."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    return opts


def cost_windows(st, k: int, seconds: float) -> Dict[str, list]:
    """``round_ms`` of ``k`` untraced windows with the recorder off and
    ``k`` with it on, alternating which goes first."""
    from benchmarks.chip import harness
    from repro.common import spans
    out = {"off": [], "on": []}
    for i in range(k):
        for on in ((False, True) if i % 2 == 0 else (True, False)):
            if on:
                spans.enable()
            try:
                win = harness.window(st, seconds)
            finally:
                spans.disable()
                spans.drain()
            out["on" if on else "off"].append(
                win["window_s"] * 1e3 / max(1, len(win["times"])))
    return out


def traced(st, rounds: int, n_devices: int):
    """``rounds`` rounds under the profiler with the recorder on:
    ``(xplane path, its directory, program side, the stage maps by the
    scopes' metadata alone, window)``."""
    import jax
    from benchmarks.chip import harness, program_trace, trace
    from repro.common import spans
    tdir = tempfile.mkdtemp(prefix="chipbench-program-")
    bench_spans: list = []
    spans.enable()
    jax.profiler.start_trace(tdir, profiler_options=profile_options())
    try:
        harness.window(st, 0.0, rounds=1)
        win = harness.window(st, 0.0, rounds=rounds, spans=bench_spans)
    finally:
        jax.profiler.stop_trace()
        spans.disable()
    rec = spans.drain()
    maps = {name: g.stage_maps() for name, g in rec.programs.items()}
    direct = {name: g.stage_maps(inherit=False)
              for name, g in rec.programs.items()}
    prog = program_trace.records_to_json(bench_spans, rec, maps)
    return trace.find_xplane(tdir), tdir, prog, direct, win


def lead_summary(pairs) -> Optional[Dict[str, Any]]:
    """The leads of one program (``ProgramTrace.leads``) in µs: how many,
    the least, median and most, and their drift over the window (µs per
    s of dispatch time, least squares)."""
    if not pairs:
        return None
    t = np.asarray([d for d, _ in pairs], np.float64) * 1e-9
    us = np.asarray([x for _, x in pairs], np.float64) * 1e-3
    slope = float(np.polyfit(t, us, 1)[0]) if np.ptp(t) > 0 else 0.0
    return {"n": len(us), "min": float(us.min()),
            "median": float(np.median(us)), "max": float(us.max()),
            "drift_us_per_s": slope}


def run(cell, *, seed: int, rounds: int, cost_k: int = 0,
        cost_seconds: float = 10.0, fixture: Optional[Path] = None,
        require_chip: bool = True) -> Dict[str, Any]:
    import jax
    from benchmarks.chip import harness, program_trace, trace, work
    from benchmarks.chip.peaks import peaks_for
    from repro.launch.env import enable_compile_cache

    devices = harness.check_devices(cell.chips, require_chip)
    dev = devices[0]
    harness.log(f"device {dev.platform} {dev.device_kind} x "
                f"{len(jax.devices())}, compile cache "
                f"{enable_compile_cache()}")
    st = harness.build(cell, seed)
    out: Dict[str, Any] = {"workload": cell.name, "seed": seed,
                           "device": {"platform": dev.platform,
                                      "kind": dev.device_kind,
                                      "count": len(jax.devices())}}
    if cost_k:
        cost = cost_windows(st, cost_k, cost_seconds)
        out["cost_round_ms"] = dict(cost, median_off=statistics.median(
            cost["off"]), median_on=statistics.median(cost["on"]))
    path, tdir, prog, direct, win = traced(st, rounds, len(devices))
    pt = program_trace.ProgramTrace.read(path, prog, n_devices=len(devices))
    red = trace.reduce(path, [tuple(s) for s in prog["bench_spans"]],
                       n_devices=len(devices))
    by_metadata = program_trace.ProgramTrace.build(
        red, pt.modules, pt.spans, pt.counters, direct)
    fl = cell.traffic["fl"]
    ctx = harness.MetricContext(
        trace=red, chips=len(devices), peaks=peaks_for(dev.device_kind),
        sels=win["sels"], macs=cell.model.layer_macs(cell.cfg),
        unit_leaves=work.unit_leaves(st.shapes, st.units),
        examples_per_client=cell.traffic["local_steps"]
        * cell.traffic["batch_size"], codec=fl.get("codec", "none"))
    out.update(
        rounds=pt.rounds(),
        metrics={n: f(pt) for n, f in program_trace.METRICS.items()},
        accepted={m["name"]: harness.load_metric(m["name"]).read(ctx)
                  for m in cell.per_layer},
        stage_ms=pt.stage_totals(),
        idle_ms_by_span=pt.idle_by_span(),
        unstaged_share=pt.unstaged_share(),
        unstaged_share_by_metadata=by_metadata.unstaged_share(),
        leads_us={p: lead_summary(v) for p, v in pt.leads().items()},
        named_idle_share=pt.named_idle_share(),
        busy_s=red.busy_s, window_s=red.window_s,
        breakdown=pt.red.breakdown(),
        failed=win["failed"])
    if fixture is not None:
        fixture.mkdir(parents=True, exist_ok=True)
        with open(path, "rb") as src, gzip.open(
                fixture / f"{cell.name}.program.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
        with gzip.open(fixture / f"{cell.name}.program.json.gz", "wt") as f:
            json.dump(prog, f)
    shutil.rmtree(tdir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--cost-windows", type=int, default=0)
    ap.add_argument("--cost-seconds", type=float, default=10.0)
    ap.add_argument("--fixture", type=Path)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    # as run.py: the benchmark is imported as a package from the root
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != Path(__file__)
        .resolve().parent]
    from benchmarks.chip import harness
    cell = harness.Cell.load(harness.load_json(ROOT / "BENCHMARK.json"),
                             args.workload)
    try:
        result = run(cell, seed=args.seed, rounds=args.rounds,
                     cost_k=args.cost_windows, cost_seconds=args.cost_seconds,
                     fixture=args.fixture)
    except harness.NoChip as e:
        print(f"trace_program.py: {e}; nothing was run", file=sys.stderr)
        return 1
    line = json.dumps(result)
    if args.out is not None:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(line + "\n")
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
