"""Round orchestration (paper Alg. 1) — the FEDn-combiner role.

The ``Server`` drives rounds at the Python level: handing shards to the
compiled ``round_step``, evaluation and history.  Everything numerically
heavy is inside the jitted round step; everything *situational* —
straggler dropout, comm accounting, logging, checkpointing — is a
composable :class:`ServerHook` rather than an inlined branch, so
deployments mix and match without touching the loop.

Hook call order per round::

    on_round_start(server, round_idx, weights) -> weights   (may reweight)
    ... compiled round step ...
    on_round_end(server, record, metrics)                   (may annotate)

If every client drops (all weights zero) the round is a recorded no-op:
the global params are untouched and the ``RoundRecord`` carries
``skipped=True`` with zero participants.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.compileguard import CompileGuard
from ..common import spans
from . import codecs, comm
from .federation import FLConfig
from .masking import UnitAssignment
from .strategies import (NormTelemetry, SelectionContext, SelectionStrategy,
                         resolve_strategy)
from .topology import Topology, resolve_topology


@dataclasses.dataclass
class RoundRecord:
    round: int
    loss: float
    eval_metric: Optional[float]
    seconds: float
    uplink_bytes: float
    trained_params: float
    n_participants: int = 0
    skipped: bool = False
    # the round's post-hook client weights (dropped clients are 0) —
    # sync rounds carry one entry per client, async flushes one per
    # buffered update; hooks and accounting read participation off this
    effective_weights: Optional[List[float]] = None
    # buffered-async flush annotations (zero on synchronous rounds)
    staleness_mean: float = 0.0
    staleness_max: float = 0.0
    sim_time: float = 0.0
    # bytes that crossed the WAN (or left a client) without landing in
    # the aggregate: quarantined uploads, duplicate deliveries, updates
    # lost in transit (DESIGN.md §14) — keeps the comm tables honest
    # under faults; wasted ⊆ uplink only for quarantined entries
    wasted_bytes: float = 0.0
    # every client dropped/crashed: the round was a recorded no-op.
    # Unlike the legacy NaN loss, dropped rounds carry loss 0.0 and are
    # excluded from EMAs and convergence summaries explicitly
    dropped: bool = False


class ServerHook:
    """Override any subset; defaults are no-ops."""

    def on_round_start(self, server: "Server", round_idx: int,
                       weights: jnp.ndarray) -> Optional[jnp.ndarray]:
        """Return new weights to reweight/drop clients, or None."""
        return None

    def on_round_end(self, server: "Server", record: RoundRecord,
                     metrics: Optional[Dict]) -> None:
        pass

    def on_fit_end(self, server: "Server",
                   history: List[RoundRecord]) -> None:
        pass


class StragglerDropout(ServerHook):
    """Simulated stragglers: each client independently drops with
    probability ``rate``; dropped clients contribute weight 0.  Draws
    from the server's key stream (reproducible per server seed)."""

    def __init__(self, rate: float):
        self.rate = rate

    def on_round_start(self, server, round_idx, weights):
        if self.rate <= 0.0:
            # a rate-0 hook must be a true no-op: drawing from the key
            # stream anyway would desync a rate=0 run from a no-hook
            # run and break bit-exact comparisons
            return None
        keep = jax.random.bernoulli(server.next_key(), 1.0 - self.rate,
                                    (server.fl.n_clients,))
        return weights * keep.astype(jnp.float32)


class CommAccounting(ServerHook):
    """Exact per-round transfer accounting (paper Table 4) from the
    round's selection matrix — fills ``uplink_bytes``/``trained_params``
    on the record.  The byte math is the server's topology plugin's
    (``Topology.round_bytes``), not hard-coded hub formulas: ``uplink``
    is whatever crosses that topology's WAN boundary (hub: client
    uploads; hierarchical: edge->hub partial aggregates; gossip: peer
    replica exchange)."""

    def on_round_end(self, server, record, metrics):
        if record.skipped or metrics is None:
            return
        # bill at wire width: the codec's encoded per-unit byte table
        # (identical to the fp32 table for codec "none").  Wasted bytes
        # (quarantine/duplicates) use the same table — a discarded
        # upload cost its *encoded* size, not fp32 width.
        ub = server.wire_unit_bytes()
        counts = comm.unit_param_counts(server.assign,
                                        server.global_params())
        if "entry_sel" in metrics:
            # buffered-async flush: one entry per buffered update, the
            # topology's buffered byte math (only flushed deltas cross
            # the WAN under hierarchical edges)
            entry_sel = np.asarray(metrics["entry_sel"])
            entry_sel = self._mask_dropped(entry_sel, record)
            record.uplink_bytes = server.topology.buffered_round_bytes(
                entry_sel, np.asarray(metrics["entry_clients"]), ub,
                server.fl)["uplink"]
            record.trained_params = float(np.einsum("cu,u->", entry_sel,
                                                    counts))
            # wasted: bytes the engine saw leave a client but never
            # aggregate (duplicates, in-transit loss) plus entries the
            # validation gate quarantined at flush time
            wasted = float(metrics.get("dropped_bytes", 0.0))
            quar = metrics.get("quarantined")
            if quar is not None:
                qm = (np.asarray(quar) > 0).astype(entry_sel.dtype)
                wasted += server.topology.buffered_round_bytes(
                    entry_sel * qm[:, None],
                    np.asarray(metrics["entry_clients"]), ub,
                    server.fl)["uplink"]
            record.wasted_bytes = wasted
            return
        sel = np.asarray(metrics["sel"])
        if sel.shape[1] != server.assign.n_units:
            # legacy no-assign shim emits a (C, 1) pseudo-unit: the
            # whole model ships for every participating client
            n_up = self._mask_dropped(np.ones((sel.shape[0], 1),
                                              sel.dtype), record).sum()
            record.uplink_bytes = float(ub.sum()) * float(n_up)
            record.trained_params = float(np.einsum("u->", counts)) \
                * float(n_up)
            return
        # bill only clients that actually uploaded: rows zeroed by
        # straggler dropout (effective weight 0) ship nothing
        sel = self._mask_dropped(sel, record)
        record.uplink_bytes = server.topology.round_bytes(
            sel, ub, server.fl)["uplink"]
        record.trained_params = float(np.einsum("cu,u->", sel, counts))
        quar = metrics.get("quarantined")
        if quar is not None:
            # quarantined clients uploaded (billed above) but their
            # deltas were discarded by the validation gate
            qm = (spans.fetch(quar, "quarantined") > 0).astype(sel.dtype)
            record.wasted_bytes = server.topology.round_bytes(
                sel * qm[:, None], ub, server.fl)["uplink"]

    @staticmethod
    def _mask_dropped(sel: np.ndarray, record) -> np.ndarray:
        eff = record.effective_weights
        if eff is None or len(eff) != sel.shape[0]:
            return sel
        keep = (np.asarray(eff, np.float32) > 0).astype(sel.dtype)
        return sel * keep[:, None]


class RoundLogger(ServerHook):
    """Print a one-line round summary every ``every`` rounds.

    ``base`` anchors the cadence: a resumed run (non-zero history base,
    e.g. after ``Federation.restore``) logs on the same relative cadence
    as a fresh one — rounds ``base``, ``base+every``, ... — and the
    final round (``total - 1``) always prints."""

    def __init__(self, every: int = 1, total: Optional[int] = None,
                 base: int = 0):
        self.every = max(1, every)
        self.total = total
        self.base = base

    def on_round_end(self, server, record, metrics):
        if record.skipped:
            # a skipped round is an anomaly worth one line regardless
            # of cadence — silent no-op rounds read as hangs
            print(f"  round {record.round:>4d} SKIPPED "
                  f"(all clients dropped)")
            return
        last = self.total is not None and record.round == self.total - 1
        if (record.round - self.base) % self.every and not last:
            return
        line = f"  round {record.round:>4d}"
        line += f" loss={record.loss:.4f}"
        if record.eval_metric is not None:
            line += f" eval={record.eval_metric:.4f}"
        line += f" uplink={record.uplink_bytes/1e6:.1f}MB"
        if record.wasted_bytes > 0.0:
            line += f" wasted={record.wasted_bytes/1e6:.1f}MB"
        if record.sim_time > 0.0:          # buffered-async flush
            line += (f" t_sim={record.sim_time:.1f}"
                     f" stale={record.staleness_mean:.2f}")
        print(line)


class Checkpointer(ServerHook):
    """Persist restartable server state every ``every`` rounds (and at
    fit end)."""

    def __init__(self, path: str, every: int = 0):
        self.path = path
        self.every = every

    def _save(self, server, pending_record=None):
        from ..ckpt import save_server_state
        save_server_state(self.path, server,
                          pending_record=pending_record)

    def on_round_end(self, server, record, metrics):
        # end hooks run before history.append, so the in-flight record
        # rides along as pending_record — without it the checkpoint
        # would pair post-round params/keys with pre-round history and
        # a resume would silently re-run the round
        if self.every and (record.round + 1) % self.every == 0:
            self._save(server, pending_record=record)

    def on_fit_end(self, server, history):
        self._save(server)


def _compact_shape(shape):
    """``(clients, rows, cols)`` for a client batch leaf of more than
    three dimensions (see ``Server.send_batches``); else its own."""
    if len(shape) <= 3:
        return shape
    return (shape[0], int(np.prod(shape[1:-2])), shape[-2] * shape[-1])


class Server:
    """``params`` is the topology *state*: the single global model for
    star topologies (hub, hierarchical), the stacked per-client replica
    tree for gossip.  ``global_params()`` is always the single-model
    view (what ``eval_fn`` sees and what accounting sizes against).
    Callers passing plain model params get them lifted into state via
    ``Topology.init_state`` (identity for star topologies)."""

    def __init__(self, round_step: Callable, assign: UnitAssignment,
                 fl: FLConfig, params, *, eval_fn: Optional[Callable] = None,
                 seed: int = 0, dropout_rate: float = 0.0,
                 hooks: Sequence[ServerHook] = (),
                 topology: Optional[Topology] = None,
                 strategy: Union[str, SelectionStrategy, None] = None):
        # the round step donates its params argument: run_round always
        # reassigns self.params from the output, so the old state is
        # dead at the call and XLA aliases it into the result instead
        # of allocating a second model-sized buffer.  CompileGuard
        # (repro.analysis.compileguard) holds the path to ONE compiled
        # program and names the retrace-triggering argument otherwise.
        self.round_step = CompileGuard(round_step, name="round_step",
                                       max_programs=1, donate_argnums=(0,))
        self.assign = assign
        self.fl = fl
        self.topology = resolve_topology(topology if topology is not None
                                         else fl.topology)
        # own the state outright (donation invalidates the buffers we
        # pass in — a caller-held reference to the init params must
        # survive the first round)
        self.params = jax.tree_util.tree_map(
            jnp.array, self.topology.init_state(params, fl))
        # where a sync round's client batches go (``place_batches``):
        # None puts them on the default device
        self._batch_split = None
        if getattr(fl, "client_shards", 0):
            # the sharded round step commits its params output to the
            # (client,) mesh; committing the initial params the same way
            # keeps round 1 and round 2 on one compiled program (the
            # uncommitted->committed flip would otherwise retrace — and
            # trip the guard)
            from jax.sharding import NamedSharding, PartitionSpec
            from ..launch.mesh import client_split, make_client_mesh
            self.params = jax.device_put(
                self.params,
                NamedSharding(make_client_mesh(fl.client_shards),
                              PartitionSpec()))
            self._batch_split = client_split(fl.client_shards)

        def unpack(arrays, shapes):
            # a function of this server's own: its guard counts only the
            # programs this server compiles
            return [x.reshape(s) for x, s in zip(arrays, shapes)]
        self._unpack = CompileGuard(unpack, name="unpack_batches",
                                    max_programs=1, static_argnums=1,
                                    out_shardings=self._batch_split)
        self.eval_fn = eval_fn
        self.key = jax.random.PRNGKey(seed)
        # the scored-selection engine (DESIGN.md §11): the server owns
        # the strategy's SelectionState pytree and threads it through
        # the compiled round step; stateless strategies keep sel_state
        # None and the round step is called exactly as before.  The
        # strategy instance is read off the round step itself when the
        # builder attached it (the instance actually baked into the
        # trace — an explicit strategy= override may differ from
        # fl.strategy), falling back to resolving the config name.
        baked = getattr(round_step, "selection_strategy", None)
        if strategy is not None:
            self.strategy = resolve_strategy(strategy, fl.synchronized)
        elif baked is not None:
            self.strategy = baked
        else:
            self.strategy = resolve_strategy(fl.strategy, fl.synchronized)
        self.sel_ctx = SelectionContext(
            n_clients=fl.n_clients, n_units=assign.n_units,
            n_train=fl.resolve_n_train(assign.n_units),
            score_ema=fl.score_ema)
        self.sel_state = self.strategy.init_state(self.sel_ctx)
        self.hooks: List[ServerHook] = [CommAccounting()]
        if dropout_rate > 0.0:
            self.hooks.append(StragglerDropout(dropout_rate))
        self.hooks.extend(hooks)
        self.history: List[RoundRecord] = []
        self.sel_history: List[np.ndarray] = []
        self._ubytes = None
        self._wire_ubytes = None
        # codec axis (core/codecs.py): the server owns the per-client
        # error-feedback residual of a stateful codec and threads it
        # through the compiled round step (None for stateless codecs);
        # checkpointed alongside sel_state for bit-exact resume
        self.codec = codecs.resolve_codec(getattr(fl, "codec", "none"))
        self.codec_state = codecs.init_codec_state(
            self.codec, self.global_params(), fl.n_clients)
        # fault axis (core/faults.py): set by the Federation facade
        # when FLConfig.faults is non-empty; owns every seeded fault
        # draw (numpy SeedSequence domain — never the jax key stream)
        self.fault_injector = None
        # buffered-async engine (core/async_agg.py); attached by the
        # Federation facade when FLConfig.async_buffer > 0
        self.async_engine = None
        # chunk-streamed cohort engine (core/cohort.py); attached when
        # FLConfig.n_registered/cohort_chunk switch the round loop over
        self.cohort_engine = None
        # history_cap retention (DESIGN.md §13): rounds trimmed off the
        # front of sel_history fold their byte/param totals here so
        # comm_summary stays exact while memory stays O(cap * cohort)
        self._sel_base = 0
        self._comm_totals = {"uplink": 0.0, "trained": 0.0, "rounds": 0}

    def next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def global_params(self):
        """Single-model view of the topology state."""
        return self.topology.global_params(self.params, self.fl)

    def unit_bytes(self) -> np.ndarray:
        if self._ubytes is None:
            self._ubytes = comm.unit_bytes(self.assign, self.global_params())
        return self._ubytes

    def wire_unit_bytes(self) -> np.ndarray:
        """Per-unit *encoded* uplink bytes under the active codec —
        what CommAccounting bills (== ``unit_bytes`` for codec
        ``none``, so non-codec accounting is unchanged)."""
        if self._wire_ubytes is None:
            self._wire_ubytes = codecs.codec_unit_bytes(
                self.codec, self.assign, self.global_params(), self.fl)
        return self._wire_ubytes

    def add_hook(self, hook: ServerHook) -> "Server":
        self.hooks.append(hook)
        return self

    def send_batches(self, client_batches):
        """Enqueue the copy of a sync round's client batches to the
        device; ``unpack_batches`` gives them back in their own shapes.

        A leaf of more than three dimensions crosses as ``(clients,
        rows, cols)``, its last two dimensions merged into ``cols``: a
        TPU pads an array's minor dimension to 128 lanes, so a VGG16
        image batch in its own shape (32 x 32 x 3) takes 4x its bytes
        there, and a round copied ahead would hold that beside the
        running round's; as ``(clients, rows, 96)`` it takes its own.
        Under ``client_shards`` each device gets only its own clients'
        rows (``launch.mesh.client_split``, the split the cohort stage
        runs over), so the copies to the devices run side by side; else
        everything goes to the default device."""
        leaves, treedef = jax.tree_util.tree_flatten(client_batches)
        shapes = tuple(tuple(x.shape) for x in leaves)
        flat = [x.reshape(_compact_shape(s)) for x, s in zip(leaves, shapes)]
        if self._batch_split is None:
            flat = [jnp.asarray(x) for x in flat]
        else:
            flat = jax.device_put(flat, self._batch_split)
        return treedef, shapes, flat

    def unpack_batches(self, sent):
        """``send_batches``' copy in its own shapes, where the round
        program reads it (a reshape on the device).  Every round is
        placed alike: the guarded round step holds one program, and a
        committed placement is part of its signature."""
        treedef, shapes, flat = sent
        return jax.tree_util.tree_unflatten(treedef,
                                            self._unpack(flat, shapes))

    def place_batches(self, client_batches):
        """A sync round's client batches on the device, in their own
        shapes (``send_batches``, then ``unpack_batches``)."""
        return self.unpack_batches(self.send_batches(client_batches))

    def run_round(self, client_batches, weights=None) -> RoundRecord:
        """client_batches: pytree with (C, steps, ...) leaves."""
        return self._run_round(lambda: self.place_batches(client_batches),
                               weights)

    def _run_round(self, load: Callable[[], Any], weights=None,
                   ahead: Optional[Callable[[int], None]] = None
                   ) -> RoundRecord:
        """One round; ``load()`` gives its client batches (called first,
        inside the round's span).  ``ahead(r + 1)``, if given, runs once
        round ``r``'s program is dispatched and before its results are
        read, so the host works while the device computes."""
        if self.async_engine is not None:
            raise RuntimeError(
                "server is in buffered-async mode (FLConfig.async_buffer "
                "> 0); a synchronous round would desync the engine's "
                "version/key bookkeeping — use run()/Federation.fit")
        if self.cohort_engine is not None:
            raise RuntimeError(
                "server is in cohort-engine mode (FLConfig.n_registered/"
                "cohort_chunk); a plain round would desync the engine's "
                "fleet/key bookkeeping — use run()/Federation.fit")
        r = len(self.history)
        with spans.round_span(r) as rspan:
            rec = self._round(r, rspan, load(), weights, ahead)
        self.history.append(rec)
        self._trim_history()
        return rec

    def _round(self, r: int, rspan, client_batches, weights,
               ahead: Optional[Callable[[int], None]]) -> RoundRecord:
        rk = self.next_key()
        c = self.fl.n_clients
        if weights is None:
            weights = jnp.ones((c,), jnp.float32)
        with spans.span("fl.hooks", phase="start"):
            for hook in self.hooks:
                new_w = hook.on_round_start(self, r, weights)
                if new_w is not None:
                    weights = new_w
        w_host = spans.fetch(weights, "weights")
        n_part = int(np.count_nonzero(w_host))
        eff_w = [float(x) for x in w_host]
        if n_part == 0:
            # every client dropped: a FedAvg denominator of zero — the
            # round is a recorded no-op, global params unchanged.  The
            # record carries loss 0.0 + dropped=True (not NaN: a NaN
            # here used to leak into logs and loss EMAs)
            rec = RoundRecord(r, 0.0, None, rspan.elapsed(), 0.0, 0.0,
                              n_participants=0, skipped=True,
                              dropped=True, effective_weights=eff_w)
            self.sel_history.append(
                np.zeros((c, self.assign.n_units), np.float32))
            metrics = None
        else:
            step_kw = {}
            inj = self.fault_injector
            if inj is not None and inj.has_delta:
                plan = inj.corrupt_plan(r, range(c))
                step_kw["fault_plan"] = {
                    "mode": jnp.asarray(plan["mode"]),
                    "scale": jnp.asarray(plan["scale"])}
            if self.codec_state is not None:
                # stateful codec: thread the EF residual through the
                # step; the new residual rides the metrics back out
                step_kw["codec_state"] = self.codec_state
            if self.sel_state is not None:
                self.params, metrics = spans.dispatch(
                    self.round_step, self.params, client_batches, weights,
                    rk, self.sel_state, **step_kw)
            else:
                self.params, metrics = spans.dispatch(
                    self.round_step, self.params, client_batches, weights,
                    rk, **step_kw)
            if "codec_state" in metrics:
                self.codec_state = metrics.pop("codec_state")
            if ahead is not None:
                ahead(r + 1)
            self.sel_history.append(spans.fetch(metrics["sel"], "sel"))
            ev = None
            if self.eval_fn is not None:
                ev = float(spans.fetch(self.eval_fn(self.global_params()),
                                       "eval"))
            rec = RoundRecord(r, float(spans.fetch(metrics["loss_mean"],
                                                   "loss")), ev,
                              rspan.elapsed(), 0.0, 0.0,
                              n_participants=n_part,
                              effective_weights=eff_w)
        with spans.span("fl.hooks", phase="end"):
            # fold the round's norm telemetry into the selection state
            # BEFORE the end-of-round hooks run, so a Checkpointer hook
            # saves the post-round state (bit-exact mid-fit resume)
            self.update_sel_state(self._round_telemetry(r, metrics, eff_w))
            for hook in self.hooks:
                hook.on_round_end(self, rec, metrics)
        rec.seconds = rspan.close()
        return rec

    def _trim_history(self) -> None:
        """Enforce ``FLConfig.history_cap``: fold selection rows older
        than the cap into running uplink/params totals and drop them,
        bounding accounting memory at O(cap * cohort) for long fits
        while keeping ``comm_summary`` exact."""
        cap = getattr(self.fl, "history_cap", 0)
        if not cap:
            return
        while len(self.sel_history) > cap:
            s = self.sel_history.pop(0)
            i = self._sel_base
            rec = self.history[i] if i < len(self.history) else None
            eff = rec.effective_weights if rec is not None else None
            if eff is not None and len(eff) == s.shape[0]:
                s = s * (np.asarray(eff, np.float32) > 0
                         ).astype(s.dtype)[:, None]
            if s.shape[1] == self.assign.n_units:
                counts = comm.unit_param_counts(self.assign,
                                                self.global_params())
                self._comm_totals["uplink"] += self.topology.round_bytes(
                    s, self.wire_unit_bytes(), self.fl)["uplink"]
                self._comm_totals["trained"] += float(
                    np.einsum("cu,u->", s, counts))
                self._comm_totals["rounds"] += 1
            if rec is not None:
                # the O(cohort) weight list already served accounting;
                # null it so long fits keep O(1) state per old round
                rec.effective_weights = None
            self._sel_base += 1

    def _round_telemetry(self, round_idx: int, metrics: Optional[Dict],
                         eff_w: Sequence[float]):
        """One sync round's NormTelemetry, or None (stateless strategy,
        skipped round, or off-cadence under FLConfig.score_every).
        Dropped clients (effective weight 0) shipped nothing and
        contribute no telemetry, matching the aggregation."""
        if self.sel_state is None or metrics is None \
                or round_idx % self.fl.score_every != 0:
            return None
        active = (np.asarray(eff_w, np.float32) > 0).astype(np.float32)
        sq = np.asarray(spans.fetch(metrics["unit_sqnorm"], "sqnorm"),
                        np.float32)
        sel = np.asarray(metrics["sel"], np.float32)
        counts = (sel * active[:, None]).sum(0)
        # synchronous participants all carry weight 1, so the weighted
        # and raw counts coincide (staleness confidence = 1)
        return NormTelemetry(unit_sqnorm=(sq * active[:, None]).sum(0),
                             unit_count=counts, unit_raw_count=counts)

    def update_sel_state(self, telemetry) -> None:
        """Advance the scored-selection state one round/flush (no-op for
        stateless strategies).  The async engine calls this per flush
        with staleness-weighted telemetry."""
        if self.sel_state is not None:
            self.sel_state = self.strategy.update_state(
                self.sel_state, self.sel_ctx, telemetry)

    def attach_async_engine(self, engine) -> "Server":
        """Switch the server to buffered-async rounds: ``run`` drives
        the engine's flush loop (one history record per flush) and
        ``comm_summary`` uses its per-flush buffered accounting."""
        self.async_engine = engine
        return self

    def attach_cohort_engine(self, engine) -> "Server":
        """Switch the server to chunk-streamed cohort rounds
        (core/cohort.py): ``run`` drives the engine's round loop; its
        records are ordinary sync records, so accounting/summary need
        no special casing."""
        self.cohort_engine = engine
        return self

    def run(self, rounds: int, batch_fn: Callable[[int], Any],
            weights=None, log_every: int = 0,
            ahead: Optional[Callable[[int], None]] = None
            ) -> List[RoundRecord]:
        """``rounds`` rounds fed by ``batch_fn``.  ``ahead`` serves the
        sync loop alone (see ``_run_round``); the async and cohort
        engines feed themselves."""
        if self.async_engine is not None:
            # buffered-async mode: batch_fn is indexed by each client's
            # own dispatch window, not a shared round counter
            return self.async_engine.run(rounds, batch_fn,
                                         weights=weights,
                                         log_every=log_every)
        if self.cohort_engine is not None:
            # cohort-engine mode: batch_fn(round_idx, client_ids) loads
            # one chunk of the sampled cohort at a time
            return self.cohort_engine.run(rounds, batch_fn,
                                          weights=weights,
                                          log_every=log_every)
        extra = [RoundLogger(log_every, total=len(self.history) + rounds,
                             base=len(self.history))] \
            if log_every else []
        self.hooks.extend(extra)
        try:
            for r in range(rounds):
                self._run_round(lambda: batch_fn(r), weights, ahead)
        finally:
            for h in extra:
                self.hooks.remove(h)
        for hook in self.hooks:
            hook.on_fit_end(self, self.history)
        return self.history

    def _wasted_summary(self) -> Dict[str, float]:
        """Fault-accounting columns (DESIGN.md §14), from the per-round
        records CommAccounting already filled."""
        per_round = [r.wasted_bytes for r in self.history]
        total = float(np.sum(per_round)) if per_round else 0.0
        return {"total_wasted_bytes": total,
                "avg_wasted_bytes": total / max(1, len(per_round))}

    def comm_summary(self) -> Dict[str, float]:
        if self.async_engine is not None and self.async_engine.started:
            return self.async_engine.comm_summary()
        if self._sel_base:
            return dict(self._capped_summary(), **self._wasted_summary())
        if not self.sel_history:
            return {"avg_uplink_bytes": 0.0, "avg_trained_params": 0.0,
                    "total_uplink_bytes": 0.0, "reduction_vs_full": 0.0,
                    "total_wasted_bytes": 0.0, "avg_wasted_bytes": 0.0}
        # selection rows of clients whose effective weight was zeroed
        # (straggler dropout) shipped nothing — mask them out so the
        # run summary matches the per-round records
        masked = []
        for i, s in enumerate(self.sel_history):
            eff = self.history[i].effective_weights \
                if i < len(self.history) else None
            if eff is not None and len(eff) == s.shape[0]:
                s = s * (np.asarray(eff, np.float32) > 0
                         ).astype(s.dtype)[:, None]
            masked.append(s)
        hist = np.stack(masked)
        if hist.shape[2] != self.assign.n_units:   # legacy no-assign shim
            per_round = [r.uplink_bytes for r in self.history]
            return dict({"avg_uplink_bytes": float(np.mean(per_round)),
                         "avg_trained_params": float(np.mean(
                             [r.trained_params for r in self.history])),
                         "total_uplink_bytes": float(np.sum(per_round)),
                         "reduction_vs_full": 0.0},
                        **self._wasted_summary())
        sum_kw = {}
        if self.codec.name != "none":
            # bill the run at encoded wire width; custom topologies
            # without the wire_ubytes parameter keep working when no
            # codec is configured
            sum_kw["wire_ubytes"] = self.wire_unit_bytes()
        return dict(self.topology.summary(self.assign,
                                          self.global_params(),
                                          hist, self.fl, **sum_kw),
                    **self._wasted_summary())

    def _capped_summary(self) -> Dict[str, float]:
        """``comm_summary`` with ``history_cap`` trimming active: the
        folded totals of trimmed rounds plus the retained window,
        through the same per-round ``Topology.round_bytes`` math — the
        result matches the uncapped summary up to float accumulation
        order (regression-tested)."""
        ub = self.unit_bytes()
        wub = self.wire_unit_bytes()
        counts = comm.unit_param_counts(self.assign, self.global_params())
        up = self._comm_totals["uplink"]
        tp = self._comm_totals["trained"]
        n = self._comm_totals["rounds"]
        for i, s in enumerate(self.sel_history):
            rec_i = self._sel_base + i
            eff = self.history[rec_i].effective_weights \
                if rec_i < len(self.history) else None
            if eff is not None and len(eff) == s.shape[0]:
                s = s * (np.asarray(eff, np.float32) > 0
                         ).astype(s.dtype)[:, None]
            up += self.topology.round_bytes(s, wub, self.fl)["uplink"]
            tp += float(np.einsum("cu,u->", s, counts))
            n += 1
        if not n:
            return {"avg_uplink_bytes": 0.0, "avg_trained_params": 0.0,
                    "total_uplink_bytes": 0.0, "reduction_vs_full": 0.0}
        # full-model uplink is a per-round constant given the cohort
        # shape, so the reduction needs no retained history
        c = self.sel_history[0].shape[0] if self.sel_history \
            else self.fl.n_clients
        full = self.topology.round_bytes(
            np.ones((c, self.assign.n_units), np.float32), ub,
            self.fl)["uplink"]
        return {
            "avg_uplink_bytes": up / n,
            "avg_trained_params": tp / n,
            "total_uplink_bytes": up,
            "reduction_vs_full": 1.0 - (up / n) / full if full else 0.0,
        }
