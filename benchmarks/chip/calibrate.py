"""Readings that the limits of ``cells/<workload>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 11 12 13 ... [--control-seeds 3] [--variants ...] [--out readings.jsonl]

For each seed, in one process: the cell's set-up (data, weights, the
Federation and its three set-up rounds, as a run makes them), then the
compared numbers of

* ``program`` — the program against the float32 reference (sound runs:
  the lower reading is their largest);
* ``control`` — the reference computed in bfloat16, put in the
  program's place (the upper reading is its smallest);
* ``default_precision`` — the float32 reference at the default matmul
  precision (one bf16 pass, as the program runs): a second witness of
  how far rounding alone parts two runs;
* ``half_batch``, ``half_cohort``, ``own_shard`` and ``flip_client`` —
  the reference with a fault planted, in the program's place: every
  client trained and scored on half of each batch; half of the cohort
  left out of the average; all but the first chip's share of the cohort
  left out (the exchange between chips missing); the first client's
  delta negated before the average.

A state left unchanged reads 1 on ``update`` and ``change`` by their
definition and needs no run.  Only the first ``--control-seeds`` seeds
run the control and the faults.  One JSON object per seed goes to
standard output (and ``--out``).  The benchmark's own runs never run
this.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
# what runs in the program's place, against the float32 reference
VARIANTS = {
    "control": {"dtype": "bfloat16", "precision": None},
    "default_precision": {"precision": None},
    "half_batch": {"fault": "half_batch"},
    "half_cohort": {"fault": "half_cohort"},
    "own_shard": {"fault": "own_shard"},
    "flip_client": {"fault": "flip_client"},
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--variants", nargs="*", default=list(VARIANTS),
                    choices=list(VARIANTS))
    ap.add_argument("--out", default=os.devnull)
    args = ap.parse_args(argv)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != Path(__file__)
        .resolve().parent]
    import jax
    import jax.numpy as jnp
    from benchmarks.chip import compare, harness
    from repro.launch.env import enable_compile_cache

    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.Cell.load(bench, args.workload)
    harness.check_devices(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with open(args.out, "a") as out:
        for i, seed in enumerate(args.seeds):
            t0 = time.perf_counter()
            st = harness.build(cell, seed)
            obs, feed, n_tr = st.observed, st.feed, st.n_train
            del st
            ref = harness.reference_readings(cell, seed, feed)
            row = {"workload": cell.name, "seed": seed,
                   "program": compare.readings(obs, ref, feed, n_tr,
                                               detail=True)}
            for name in args.variants if i < args.control_seeds else ():
                kw = dict(VARIANTS[name])
                if "dtype" in kw:
                    kw["dtype"] = jnp.dtype(kw["dtype"])
                alt = harness.reference_readings(cell, seed, feed, **kw)
                row[name] = compare.readings(alt, ref, feed, n_tr,
                                             detail=True)
            row["seconds"] = time.perf_counter() - t0
            line = json.dumps(row)
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
