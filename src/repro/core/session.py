"""The ``Federation`` facade: one object that owns a federated run.

Every entry point (launcher, example, benchmark) used to re-implement
the same wiring by hand: model init -> unit assignment -> loader ->
``build_round_step`` -> ``Server``.  ``Federation.from_config`` does
that wiring once, for both model worlds:

* a zoo :class:`ArchConfig` (``repro.configs``) — model comes from
  ``repro.models.get_model``, units from ``build_units_zoo``;
* a :class:`ModelSpec` — any hand-rolled model (the paper's VGG16 /
  IMDB / CASA live in ``repro.models.paper_models``), units from
  ``build_units_flat``.

Usage::

    fed = Federation.from_config(cfg, fl, data=loader, eval_fn=acc)
    fed.fit(rounds=20, log_every=1)
    fed.comm_summary()

Strategy selection is the registered-plugin name in ``fl.strategy``
(see core/strategies.py); the federation topology is the registered
plugin name in ``fl.topology`` (core/topology.py: ``hub`` |
``hierarchical`` | ``gossip``).  Pass ``strategy=`` / ``topology=`` to
override either with an unregistered instance.  Cross-cutting behaviour
(straggler dropout, checkpointing, logging, custom metrics) attaches as
``ServerHook``s.

The sparse round step (DESIGN.md §7) is two more ``FLConfig`` knobs
that flow straight through the facade: ``packed=True`` runs
hub/hierarchical rounds on packed trained-unit slot buffers (zero
optimizer state for frozen stacked rows, shrunken cross-client
reduce — bit-exact with the default dense-masked path), and
``fused_agg`` selects the fused Pallas aggregation kernel ("auto":
compiled on TPU/GPU, jnp reference elsewhere).

Semi-async buffered rounds (DESIGN.md §8) are three further knobs:
``async_buffer=K`` switches ``fit`` to FedBuff-style flush rounds (K
buffered packed updates per global step), ``staleness``/
``staleness_alpha`` pick the registered stale-delta reweighting rule,
and ``client_delay_dist`` the simulated client-latency distribution
(``"pareto[:a]"`` for the heavy-tailed straggler regime).

Scored selection (DESIGN.md §11) needs no knob at all beyond the
strategy name: a stateful strategy (``score_weighted`` /
``depth_dropout`` / ``successive``) makes the ``Server`` own a
``SelectionState`` pytree, turns on the gradient-norm telemetry inside
the round step, and checkpoints carry the state (bit-exact mid-fit
restore).  ``score_ema`` / ``score_every`` tune the EMA decay and the
update cadence.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from ..common import spans
from ..data import FederatedLoader
from .federation import FLConfig, build_round_step
from .masking import UnitAssignment, build_units_flat, build_units_zoo
from .server import RoundRecord, Server, ServerHook
from .strategies import SelectionStrategy
from .topology import Topology, resolve_topology

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """A model described by plain functions (non-zoo architectures).

    ``unit_order`` is either the explicit freeze-unit order (top-level
    param keys) or a callable ``params -> order`` (e.g.
    ``paper_models.vgg16_units``).
    """
    name: str
    init_params: Callable[[Any], PyTree]            # PRNGKey -> params
    loss_fn: Callable                               # (params, batch) -> (loss, aux)
    unit_order: Union[Sequence[str], Callable[[PyTree], Sequence[str]]]


class Federation:
    """Owns params, unit assignment, compiled round step, server, data."""

    def __init__(self, *, loss_fn: Callable, params: PyTree,
                 assign: UnitAssignment, fl: FLConfig,
                 loader: Optional[FederatedLoader] = None,
                 eval_fn: Optional[Callable] = None,
                 loss_kwargs: Optional[Dict] = None, seed: int = 0,
                 dropout_rate: float = 0.0,
                 hooks: Sequence[ServerHook] = (),
                 strategy: Union[str, SelectionStrategy, None] = None,
                 scores: Optional[jnp.ndarray] = None,
                 topology: Union[str, Topology, None] = None,
                 incarnation: int = 0):
        self.fl = fl
        self.assign = assign
        self.loader = loader
        # the sync engine's one-round-ahead feed (DESIGN.md §13): round
        # r+1's batches, built and copied to the device while round r
        # computes, as (loader, round, ``Server.send_batches``' copy);
        # kept across fit calls, emptied by restore and run_round
        self._staged = None
        # sync rounds fed from that slot and rounds built in place
        self.feed_counts = {"staged": 0, "built": 0}
        self.topology = resolve_topology(topology if topology is not None
                                         else fl.topology)
        round_step = build_round_step(loss_fn, assign, fl, loss_kwargs,
                                      strategy=strategy, scores=scores,
                                      topology=self.topology)
        self.server = Server(round_step, assign, fl, params,
                             eval_fn=eval_fn, seed=seed,
                             dropout_rate=dropout_rate, hooks=hooks,
                             topology=self.topology, strategy=strategy)
        # fault-injection chaos axis (DESIGN.md §14): the injector is a
        # pure function of (seed, incarnation, coordinates), so a
        # restarted process with incarnation+1 replays a *different*
        # kill schedule while the training key streams stay identical
        injector = None
        if fl.faults:
            from .faults import FaultInjector
            injector = FaultInjector(fl.faults, seed=seed,
                                     incarnation=incarnation)
        self.server.fault_injector = injector
        if fl.async_buffer:
            # semi-async buffered rounds (DESIGN.md §8): the engine owns
            # the simulated-delay scheduler, per-version selection keys
            # and the FedBuff-style buffer; one fit "round" = one flush
            from .async_agg import AsyncRoundEngine, build_cohort_step
            from .faults import gate_enabled
            select_fn, cohort_fn, _ = build_cohort_step(
                loss_fn, assign, fl, loss_kwargs, strategy=strategy,
                scores=scores)
            base_flush = self.topology.build_buffered_flush(assign, fl)
            flush_fn, gated = base_flush, False
            if gate_enabled(fl):
                from .aggregation import gate_packed_updates

                def flush_fn(g, pdeltas, rows, valid, sel, weights,
                             clients, _base=base_flush):
                    pdeltas, gw, quar = gate_packed_updates(
                        assign, pdeltas, valid, weights,
                        fl.max_delta_norm)
                    return _base(g, pdeltas, rows, valid, sel, gw,
                                 clients), quar
                gated = True
            self.server.attach_async_engine(AsyncRoundEngine(
                self.server, assign, fl, select_fn=select_fn,
                cohort_fn=cohort_fn, flush_fn=flush_fn,
                seed=seed, gated=gated))
        if fl.uses_cohort_engine():
            # fleet-scale cohort engine (DESIGN.md §13): samples the
            # round's cohort out of n_registered clients and streams it
            # through the round in cohort_chunk-sized compiled chunks
            # (mutually exclusive with async_buffer — FLConfig validates)
            from .cohort import CohortEngine, build_cohort_programs
            programs = build_cohort_programs(
                loss_fn, assign, fl, loss_kwargs, strategy=strategy,
                scores=scores, topology=self.topology)
            self.server.attach_cohort_engine(CohortEngine(
                self.server, assign, fl, programs=programs, seed=seed))
        if injector is not None:
            # appended LAST so a user Checkpointer hook has already
            # saved the round before an injected kill can raise
            from .faults import ChaosHook
            self.server.hooks.append(ChaosHook(injector))

    # -- construction -----------------------------------------------------

    @classmethod
    def from_config(cls, cfg, fl: FLConfig, *, data=None, seed: int = 0,
                    eval_fn: Optional[Callable] = None,
                    loss_kwargs: Optional[Dict] = None,
                    batch_size: int = 8, steps_per_round: int = 2,
                    **kwargs) -> "Federation":
        """Wire a full federated run from a config.

        ``cfg`` is a zoo ``ArchConfig`` or a :class:`ModelSpec`.
        ``data`` is a :class:`FederatedLoader`, or a list of per-client
        array dicts (then ``batch_size``/``steps_per_round`` apply), or
        None (supply batches to ``run_round`` yourself).
        Remaining ``kwargs`` go to the constructor (hooks,
        dropout_rate, strategy, scores, topology).
        """
        key = jax.random.PRNGKey(seed)
        if isinstance(cfg, ModelSpec):
            params = cfg.init_params(key)
            order = cfg.unit_order(params) if callable(cfg.unit_order) \
                else list(cfg.unit_order)
            assign = build_units_flat(params, order)
            loss_fn = cfg.loss_fn
        elif hasattr(cfg, "family"):
            from ..models import get_model
            model = get_model(cfg)
            params = model.init_params(key)
            assign = build_units_zoo(cfg, params)
            loss_fn = model.loss_fn
            if loss_kwargs is None:
                # CPU-host default; pod launchers pass their own
                loss_kwargs = {} if cfg.family == "ssm" else \
                    {"attn_impl": "reference"}
        else:
            raise TypeError(
                f"cfg must be an ArchConfig or ModelSpec, got {type(cfg)}")
        loader = cls._as_loader(data, batch_size=batch_size,
                                steps_per_round=steps_per_round, seed=seed)
        return cls(loss_fn=loss_fn, params=params, assign=assign, fl=fl,
                   loader=loader, eval_fn=eval_fn, loss_kwargs=loss_kwargs,
                   seed=seed, **kwargs)

    @staticmethod
    def _as_loader(data, *, batch_size: int, steps_per_round: int,
                   seed: int) -> Optional[FederatedLoader]:
        if data is None or isinstance(data, FederatedLoader):
            return data
        return FederatedLoader(list(data), batch_size=batch_size,
                               steps_per_round=steps_per_round, key=seed)

    # -- the run ----------------------------------------------------------

    def fit(self, rounds: int, *, log_every: int = 0,
            weights=None) -> List[RoundRecord]:
        """Run ``rounds`` federated rounds off the attached loader.

        In buffered-async mode (``fl.async_buffer > 0``) a "round" is
        one buffer flush, and the loader is indexed by each client's own
        dispatch window (the engine carries per-client counters across
        ``fit`` calls and restores), not a shared round counter.

        A sync round feeds one round ahead: while round r runs on the
        device, the host builds round r+1's batches and enqueues their
        copy (``_stage``); round r+1 takes them from that slot
        (``_take``).  A round's rows depend only on its index, so the
        result is bitwise that of building each round in place.
        """
        if self.loader is None:
            raise ValueError("Federation has no data attached; pass "
                             "data= to from_config or use run_round")
        if weights is None:
            weights = jnp.asarray(self.loader.weights())
        if self.server.cohort_engine is not None:
            # cohort-engine mode: the loader holds the registered fleet
            # and serves one chunk of sampled clients at a time; the
            # engine indexes it by absolute round (resume-safe), so no
            # history base is added here
            return self.server.run(
                rounds, lambda r, ids: self._feed(self.loader.client_batches,
                                                  r, ids),
                weights=weights, log_every=log_every)
        if self.server.async_engine is not None:
            # indexed by each client's dispatch window: nothing to stage
            return self.server.run(
                rounds, lambda r: self._feed(self.loader.round_batches, r),
                weights=weights, log_every=log_every)
        base = len(self.server.history)
        return self.server.run(rounds, lambda r: self._take(base + r),
                               weights=weights, log_every=log_every,
                               ahead=self._stage)

    @staticmethod
    def _feed(build: Callable, *args) -> PyTree:
        """``build(*args)``'s host batches, copied to the device."""
        with spans.span("fl.feed"):
            with spans.span("fl.feed.build"):
                host = build(*args)
            with spans.span("fl.feed.h2d"):
                return jax.tree_util.tree_map(jnp.asarray, host)

    def _take(self, rnd: int) -> PyTree:
        """Sync round ``rnd``'s batches on the device: from the slot
        where the round before staged them, else built in place (the
        first round, or after ``run_round``, ``restore`` or a new
        loader)."""
        slot, self._staged = self._staged, None
        staged = slot is not None and slot[0] is self.loader \
            and slot[1] == rnd
        self.feed_counts["staged" if staged else "built"] += 1
        with spans.span("fl.feed", staged=staged):
            if not staged:
                return self._build(rnd, self.server.place_batches)
            # the children hand over what round rnd - 1 built and
            # copied, reshaped on the device once that round is done
            with spans.span("fl.feed.build"):
                pass
            with spans.span("fl.feed.h2d"):
                return self.server.unpack_batches(slot[2])

    def _build(self, rnd: int, place: Callable) -> Any:
        """Sync round ``rnd``'s batches, built on the host and handed to
        ``place`` (a ``Server`` method that enqueues their copy)."""
        with spans.span("fl.feed.build"):
            host = self.loader.round_batches(rnd)
        with spans.span("fl.feed.h2d"):
            return place(host)

    def _stage(self, rnd: int) -> None:
        """Build round ``rnd``'s batches and enqueue their copy while
        round ``rnd - 1`` runs on the device (``Server._round`` calls
        this between the dispatch and the first read of its results)."""
        with spans.span("fl.feed.ahead", round=rnd):
            self._staged = (self.loader, rnd,
                            self._build(rnd, self.server.send_batches))

    def run_round(self, client_batches, weights=None) -> RoundRecord:
        self._staged = None
        return self.server.run_round(client_batches, weights)

    def evaluate(self) -> Optional[float]:
        if self.server.eval_fn is None:
            return None
        return float(self.server.eval_fn(self.server.global_params()))

    def comm_summary(self) -> Dict[str, float]:
        return self.server.comm_summary()

    # -- state ------------------------------------------------------------

    @property
    def params(self) -> PyTree:
        """Single-model view (the mean replica under gossip)."""
        return self.server.global_params()

    @property
    def state(self) -> PyTree:
        """The raw topology state the server carries across rounds."""
        return self.server.params

    @property
    def history(self) -> List[RoundRecord]:
        return self.server.history

    def save(self, path: str, extra: Optional[Dict] = None) -> None:
        from ..ckpt import save_server_state
        save_server_state(path, self.server, extra=extra)

    def restore(self, path: str) -> Dict:
        from ..ckpt import restore_server_state
        self._staged = None
        return restore_server_state(path, self.server)
