"""Cells of the chip benchmark cut to a size the CPU runs in seconds:
the same files, engines and comparison, with narrow layers, a few
clients and few rows."""
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.chip import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny_cell(workload: str) -> "harness.Cell":
    cell = harness.Cell.load(BENCH, workload)
    cfg = copy.deepcopy(cell.cfg)
    tr = copy.deepcopy(cell.traffic)
    if "stages" in cfg:
        cfg["stages"] = [[n, 8] for n, _ in cfg["stages"]]
        cfg["examples_per_client"] = 16
        tr["fl"]["n_clients"] = 8
        tr.update(batch_size=4, local_steps=2)
    else:
        cfg.update(vocab=600, embed=16, conv_filters=8, lstm=6,
                   reference_chunk=2)
        tr["fl"].update(n_clients=4, n_registered=10, cohort_chunk=2)
    tr.update(uplink_rounds=2, trace_rounds=2)
    cell.cfg, cell.traffic = cfg, tr
    return cell


def _half(batches):
    return jax.tree_util.tree_map(lambda x: x[:, : x.shape[1] // 2], batches)


def _first_half(w):
    k = w.shape[0]
    return w * (jax.numpy.arange(k) < k // 2)


def _per_chunk(w, chunk):
    return w * (jax.numpy.arange(w.shape[0]) % chunk < chunk // 2)


def plant(fault, monkeypatch, chunk=0):
    """Break the program under the harness (no file of it changes)."""
    import repro.core.client as client
    import repro.core.topology as topo
    if fault == "unchanged":
        monkeypatch.setattr(topo, "masked_fedavg",
                            lambda g, d, sel, w, assign: g)
        monkeypatch.setattr(topo, "packed_finalize",
                            lambda assign, g, *a, **kw: g)
    elif fault == "half_batch":
        dense, packed = topo.local_update, client.local_update_packed
        monkeypatch.setattr(topo, "local_update",
                            lambda f, g, m, b, **kw: dense(f, g, m, _half(b),
                                                           **kw))
        monkeypatch.setattr(client, "local_update_packed",
                            lambda f, g, a, r, v, b, **kw:
                            packed(f, g, a, r, v, _half(b), **kw))
    elif fault == "half_cohort":
        # the second half of the cohort (of each chunk, on the cohort
        # engine) left out; the mean taken over the rest
        agg, acc, fin = (topo.masked_fedavg, topo.packed_accumulate,
                         topo.packed_finalize)
        monkeypatch.setattr(topo, "masked_fedavg",
                            lambda g, d, sel, w, assign:
                            agg(g, d, sel, _first_half(w), assign))
        monkeypatch.setattr(topo, "packed_accumulate",
                            lambda assign, a, d, r, v, w, **kw:
                            acc(assign, a, d, r, v, _first_half(w), **kw))
        monkeypatch.setattr(topo, "packed_finalize",
                            lambda assign, g, a, sel, w, **kw:
                            fin(assign, g, a, sel, _per_chunk(w, chunk),
                                **kw))
    elif fault == "no_exchange":
        # each chip keeps its own clients' deltas: the others' arrive as
        # zeros, as if the gather between chips were left out
        runner = topo._cohort_runner

        def own_only(fl, width):
            run = runner(fl, width)
            keep = jax.numpy.arange(width) < width // fl.client_shards

            def masked(fn, gp, *per_client):
                deltas, metrics = run(fn, gp, *per_client)
                return jax.tree_util.tree_map(
                    lambda d: d * keep.reshape((-1,) + (1,) * (d.ndim - 1)),
                    deltas), metrics
            return masked
        monkeypatch.setattr(topo, "_cohort_runner", own_only)
    elif fault in ("other_client", "labels_shifted", "repeated_batch"):
        # the loader serves the next client's rows, labels one row out
        # of line with their inputs, or its first batch at every step
        from repro.data import FederatedLoader
        serve = FederatedLoader.client_batches

        def faulty(self, rnd, ids):
            if fault == "other_client":
                return serve(self, rnd, [(int(i) + 1) % self.n_clients
                                         for i in ids])
            out = serve(self, rnd, ids)
            if fault == "labels_shifted":
                return dict(out, y=np.roll(out["y"], 1, axis=2))
            return {k: np.repeat(v[:, :1], v.shape[1], axis=1)
                    for k, v in out.items()}
        monkeypatch.setattr(FederatedLoader, "client_batches", faulty)
    elif fault == "flip_client":
        agg, acc = topo.masked_fedavg, topo.packed_accumulate
        flip = lambda d: jax.tree_util.tree_map(
            lambda x: x.at[0].multiply(-1.0), d)
        monkeypatch.setattr(topo, "masked_fedavg",
                            lambda g, d, *a: agg(g, flip(d), *a))
        monkeypatch.setattr(topo, "packed_accumulate",
                            lambda assign, a, d, *r, **kw:
                            acc(assign, a, flip(d), *r, **kw))
