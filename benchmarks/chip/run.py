"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, traffic,
limits and per-layer metrics are found by name from ``BENCHMARK.json``
(see harness.py).  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics from a
profiler trace of the window.  The numbers compared with the reference
are the last lines on standard error, and the last key of the result.

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the program is not beside the
benchmark.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    # the script's own directory would shadow the standard library's
    # ``trace``; the benchmark is imported as a package from the root
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != Path(__file__)
        .resolve().parent]
    from benchmarks.chip import harness

    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.Cell.load(bench, args.workload)
    try:
        result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                             traced=bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 1
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
