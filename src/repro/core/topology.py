"""Federation topologies as registered plugins (DESIGN.md §6).

PR 1 made *what each client trains* pluggable (core/strategies.py);
this module makes *how updates flow between nodes* pluggable the same
way.  A **topology** owns three cross-layer responsibilities:

1. the compiled round step — where its aggregation stage lives
   (``build_round_step``; hub and hierarchical share the star skeleton
   and differ only in the aggregation callback, gossip carries
   per-client replicas instead of one global model);
2. its exact byte accounting (``round_bytes``/``summary`` route
   ``CommAccounting`` and ``Server.comm_summary`` through the plugin
   instead of hard-coded hub math — core/comm.py has the formulas);
3. its mesh view (``make_mesh``: launch/mesh.py grows an edge-group
   axis carve-out for hierarchical).

Registered plugins:

* ``hub`` — the paper's FEDn combiner star (the default).  Its round
  step is the exact trace PR 1 compiled, so results are bit-exact with
  the pre-topology path (regression-tested).
* ``hierarchical`` — clients partitioned under ``FLConfig.n_edges``
  edge aggregators; two-stage masked FedAvg (per-edge partial
  aggregates, then hub combine) inside the single compiled round step.
  Only the per-edge selection *union* crosses the edge->hub WAN link,
  compounding the paper's partial-update savings.
* ``gossip`` — hubless peer averaging over a doubly-stochastic ring
  mixing matrix; per-client parameter replicas are the server state and
  are carried across rounds (``stateful = True``).

Adding a topology is a subclass + ``@register_topology`` — no change to
``federation.py``, ``Server``, launchers or benchmarks.

The star topologies additionally own the **sparse round step**
(DESIGN.md §7): ``FLConfig.packed`` swaps the masked local update and
aggregation for their packed slot-buffer variants (bit-exact,
regression-tested), and ``FLConfig.fused_agg`` routes the aggregation
stage through the fused Pallas kernel (``kernels/masked_agg``) with
the tiling plan hoisted to build time.

They also own the **buffered-async flush** (DESIGN.md §8, the third
plugin axis in ``core/async_agg.py``): ``build_buffered_flush`` is the
topology's aggregation stage over a stacked buffer of packed updates,
and ``buffered_round_bytes`` its per-flush byte math (hierarchical:
only flushed per-edge partials cross the WAN).  Gossip has no global
model to buffer against and rejects ``FLConfig.async_buffer``.
"""
from __future__ import annotations

from typing import (Any, Callable, ClassVar, Dict, Optional, Tuple, Type,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

import dataclasses

from . import comm
from .aggregation import (F32, fedavg, gate_packed_updates,
                          hierarchical_edge_partials,
                          hierarchical_masked_fedavg,
                          hierarchical_masked_fedavg_packed, masked_fedavg,
                          masked_fedavg_packed, packed_acc_init,
                          packed_accumulate, packed_finalize)
from .client import local_update, packed_cohort_fn
from .masking import (UnitAssignment, dense_norm_hook, mask_tree,
                      slot_plan)
from .registry import unknown_name_message
from .strategies import SelectionContext, resolve_strategy

PyTree = Any


def ring_mixing_matrix(n: int) -> np.ndarray:
    """Doubly-stochastic Metropolis weights on a ring of ``n`` peers.

    n=1 -> identity; n=2 -> exact pair averaging; n>=3 -> 1/3 self +
    1/3 to each ring neighbour.  Rows AND columns sum to one, so the
    uniform average of the replicas is invariant under mixing.
    """
    if n < 1:
        raise ValueError("ring needs at least one peer")
    if n == 1:
        return np.ones((1, 1), np.float32)
    if n == 2:
        return np.full((2, 2), 0.5, np.float32)
    w = np.eye(n, dtype=np.float32) / 3.0
    w += np.roll(np.eye(n, dtype=np.float32), 1, axis=1) / 3.0
    w += np.roll(np.eye(n, dtype=np.float32), -1, axis=1) / 3.0
    return w


def _selection_setup(assign: UnitAssignment, fl, strategy, scores):
    """Shared preamble of every topology's round step: resolve the
    strategy, validate n_train, build the static selection context."""
    strat = resolve_strategy(strategy if strategy is not None
                             else fl.strategy, fl.synchronized)
    n_train = fl.resolve_n_train(assign.n_units)
    if not strat.dense and not 1 <= n_train <= assign.n_units:
        raise ValueError(
            f"n_train={n_train} out of range for {assign.n_units} units; "
            "set FLConfig.n_train_units or train_fraction")
    ctx = SelectionContext(n_clients=fl.n_clients, n_units=assign.n_units,
                           n_train=n_train, scores=scores,
                           score_ema=getattr(fl, "score_ema", 0.9))
    return strat, ctx


def _live_ctx(ctx: SelectionContext, sel_state) -> SelectionContext:
    """Swap the build-time context for the round's live selection state
    (traced arrays) when the server threads one in."""
    if sel_state is None:
        return ctx
    return dataclasses.replace(ctx, scores=sel_state.scores,
                               state=sel_state)


def _cohort_runner(fl, width: int) -> Callable:
    """How a round step runs its vmapped cohort stage: directly on one
    device, or split over the ``(client,)`` mesh when
    ``fl.client_shards`` is set (DESIGN.md §13).  ``run(fn, gp,
    *per_client)`` — ``gp`` replicated, everything else carrying a
    leading ``width`` client axis.  The stage's outputs are gathered to
    every device, so the reduce that follows sums in one device's order:
    both paths agree bitwise wherever a row compiles the same at either
    vmap width (a conv model need not, see DESIGN.md §13).  The price is
    that every device receives the whole cohort's deltas each round and
    runs the reduce itself.
    """
    shards = getattr(fl, "client_shards", 0)
    if not shards:
        return lambda fn, gp, *per_client: fn(gp, *per_client)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..launch.mesh import make_client_mesh, shard_over_clients

    def run(fn, gp, *per_client):
        out = shard_over_clients(fn, shards, width)(gp, *per_client)
        # what follows (FedAvg, the mean loss) sees the whole cohort on
        # every device, so it sums in the order one device would; the
        # gather that brings it there is the aggregation's
        whole = NamedSharding(make_client_mesh(shards), P())
        with jax.named_scope("fl/aggregate"):
            return jax.lax.with_sharding_constraint(out, whole)

    return run


def _star_round_step(loss_fn: Callable, assign: UnitAssignment, fl,
                     loss_kwargs: Optional[Dict], *, strategy, scores,
                     aggregate: Callable, aggregate_dense: Callable,
                     aggregate_packed: Optional[Callable] = None):
    """The star-topology skeleton: selection -> vmapped masked local
    training -> a topology-supplied aggregation stage.

    ``aggregate(global_params, deltas, sel, weights)`` is the masked
    path; ``aggregate_dense`` the dense (full-strategy) path.  The hub
    plugin passes ``masked_fedavg``/``fedavg`` so its trace is exactly
    the pre-topology round step (bit-exactness is regression-tested).

    With ``fl.packed`` (DESIGN.md §7) local training and aggregation
    run on packed slot buffers instead: ``aggregate_packed(g,
    packed_deltas, rows, valid, sel, weights)`` reduces only the
    ``n_slots`` trained units per client.  The slot budget ``n_slots``
    is static (``n_train`` plus the optional always-trained head), so
    all packed shapes are static under vmap/scan.

    Stateful (scored) strategies get two extra wires (DESIGN.md §11),
    both compiled out entirely for stateless strategies (their trace is
    the pre-scoring trace, bit-exact): the optional ``sel_state``
    argument threads the live :class:`SelectionState` into the
    selection context, and the metrics carry ``unit_sqnorm`` — (C, U)
    per-client per-unit squared gradient norms accumulated by the
    local-update norm hook from gradients the step already
    materialized.
    """
    strat, ctx = _selection_setup(assign, fl, strategy, scores)
    use_packed = fl.packed and not strat.dense
    if use_packed and aggregate_packed is None:
        raise ValueError(
            f"topology {fl.topology!r} has no packed aggregation path; "
            "set FLConfig.packed=False")
    # the fault axis (core/faults.py): delta corruption + the validation
    # gate are compiled into the packed branch only — both are bitwise
    # identities when untripped, so a zero-rate chaos config keeps the
    # plain trace's numbers exactly
    from . import faults as _faults
    inject_on = _faults.delta_faults_configured(fl)
    gate_on = _faults.gate_enabled(fl)
    if (inject_on or gate_on) and not use_packed:
        raise ValueError(
            "delta faults / the validation gate run inside the packed "
            "scatter-accumulate; set FLConfig.packed=True (or drop "
            "delta faults and max_delta_norm)")
    n_slots = fl.resolve_n_slots(ctx.n_units)
    scoring = strat.stateful
    run_cohort = _cohort_runner(fl, fl.n_clients)
    packed_cohort = packed_cohort_fn(loss_fn, assign, fl, loss_kwargs,
                                     scoring=scoring)
    # the codec axis (core/codecs.py): encode/decode round-trips the
    # packed deltas before they "cross the WAN" (= before corruption /
    # gating / aggregation).  codec "none" builds no transform and the
    # trace is bitwise the pre-codec one.
    from . import codecs as _codecs
    codec = _codecs.resolve_codec(fl.codec)
    codec_fn = _codecs.build_codec_transform(codec, assign, fl)

    def dense_cohort(gp, client_batches):
        hook = dense_norm_hook(assign) if scoring else None
        ones_mask = jax.tree_util.tree_map(
            lambda x: jnp.ones((), jnp.float32), gp)

        def one_client_dense(batches):
            return local_update(loss_fn, gp, ones_mask, batches, lr=fl.lr,
                                optimizer=fl.optimizer, prox_mu=fl.prox_mu,
                                loss_kwargs=loss_kwargs, norm_hook=hook)

        return jax.vmap(one_client_dense)(client_batches)

    def masked_cohort(gp, sel, client_batches):
        hook = dense_norm_hook(assign) if scoring else None

        def one_client(sel_row, batches):
            mask = mask_tree(assign, sel_row, gp)
            return local_update(loss_fn, gp, mask, batches, lr=fl.lr,
                                optimizer=fl.optimizer, prox_mu=fl.prox_mu,
                                loss_kwargs=loss_kwargs, norm_hook=hook)

        return jax.vmap(one_client)(sel, client_batches)

    def round_step(global_params, client_batches, weights, round_key,
                   sel_state=None, fault_plan=None, codec_state=None):
        # each stage under its scope (fl/<stage>): the op metadata that
        # names the stage of every device operation in a trace
        with jax.named_scope("fl/select"):
            sel = strat.select(round_key, _live_ctx(ctx, sel_state))
            if fl.always_train_head:
                sel = sel.at[:, -1].set(1.0)

        quarantined = None
        new_codec_state = None
        if strat.dense:
            # every unit trained: unmasked local step + the topology's
            # dense aggregation — for hub, bit-exact with the
            # conventional-FedAvg baseline trace
            with jax.named_scope("fl/local_train"):
                deltas, metrics = run_cohort(dense_cohort, global_params,
                                             client_batches)
            with jax.named_scope("fl/aggregate"):
                new_params = aggregate_dense(global_params, deltas, sel,
                                             weights)
        elif use_packed:
            with jax.named_scope("fl/slot_plan"):
                rows, valid = jax.vmap(
                    lambda s: slot_plan(assign, s, n_slots,
                                        global_params))(sel)
            with jax.named_scope("fl/local_train"):
                pdeltas, metrics = run_cohort(packed_cohort, global_params,
                                              rows, valid, client_batches)
            if codec_fn is not None:
                with jax.named_scope("fl/codec"):
                    ck = jax.random.fold_in(round_key,
                                            _codecs.CODEC_KEY_TAG)
                    decay = jnp.ones((fl.n_clients,), jnp.float32)
                    pdeltas, new_codec_state = codec_fn(
                        pdeltas, rows, valid, weights, ck, codec_state,
                        decay)
            if inject_on:
                with jax.named_scope("fl/faults"):
                    if fault_plan is None:
                        fault_plan = {
                            "mode": jnp.zeros((fl.n_clients,), jnp.int32),
                            "scale": jnp.ones((fl.n_clients,),
                                              jnp.float32)}
                    pdeltas = _faults.chaos_inject(
                        pdeltas, fault_plan["mode"], fault_plan["scale"])
            if gate_on:
                with jax.named_scope("fl/gate"):
                    pdeltas, weights, quarantined = gate_packed_updates(
                        assign, pdeltas, valid, weights, fl.max_delta_norm)
            with jax.named_scope("fl/aggregate"):
                new_params = aggregate_packed(global_params, pdeltas, rows,
                                              valid, sel, weights)
        else:
            with jax.named_scope("fl/local_train"):
                deltas, metrics = run_cohort(masked_cohort, global_params,
                                             sel, client_batches)
            with jax.named_scope("fl/aggregate"):
                new_params = aggregate(global_params, deltas, sel, weights)
        with jax.named_scope("fl/finalize"):
            out_metrics = {
                "loss_mean": metrics["loss_mean"].mean(),
                "loss_per_client": metrics["loss_mean"],
                "loss_steps": metrics["loss_steps"],
                "sel": sel,
            }
        if scoring:
            out_metrics["unit_sqnorm"] = metrics["unit_sqnorm"]
        if quarantined is not None:
            out_metrics["quarantined"] = quarantined
        if new_codec_state is not None:
            out_metrics["codec_state"] = new_codec_state
        return new_params, out_metrics

    # the Server derives state ownership from the strategy actually
    # baked into this step (a strategy= override might differ from
    # fl.strategy; re-resolving the name there would silently desync)
    round_step.selection_strategy = strat
    return round_step


def _whole_on_each_device(fn: Callable, fl) -> Callable:
    """A Pallas kernel cannot be partitioned across devices: when
    ``fl.client_shards`` splits the cohort over the ``(client,)`` mesh,
    run ``fn`` whole on every device of it, on replicated inputs."""
    shards = getattr(fl, "client_shards", 0)
    if not shards:
        return fn
    from jax.sharding import PartitionSpec as P
    from ..launch.mesh import make_client_mesh
    return jax.shard_map(fn, mesh=make_client_mesh(shards), in_specs=P(),
                         out_specs=P(), check_vma=False)


def _fused_hub_aggregate(assign: UnitAssignment, fl) -> Callable:
    """Masked FedAvg through the fused Pallas kernel, with the per-leaf
    tiling plan hoisted out of the traced function: built once at the
    first trace (shapes only) and reused for every retrace/call."""
    from ..kernels.masked_agg.ops import build_agg_plan, masked_fedavg_fused
    cache: Dict[str, Any] = {}

    def aggregate(g, d, sel, w):
        if "plan" not in cache:
            cache["plan"] = build_agg_plan(assign, g)
        return masked_fedavg_fused(g, d, sel, w, assign,
                                   plan=cache["plan"])

    return _whole_on_each_device(aggregate, fl)


def _fused_hier_aggregate(assign: UnitAssignment, mem, fl) -> Callable:
    """Two-stage masked FedAvg with the hub combine running through the
    fused kernel: jnp per-edge partial means (stage 1), then the Pallas
    combine over edges with the per-edge weight mass as ``wsel``."""
    from ..kernels.masked_agg.ops import build_agg_plan, masked_combine_fused
    cache: Dict[str, Any] = {}

    def aggregate(g, d, sel, w):
        if "plan" not in cache:
            cache["plan"] = build_agg_plan(assign, g)
        means, e_den = hierarchical_edge_partials(d, sel, w, assign, mem)
        return masked_combine_fused(g, means, e_den, assign,
                                    plan=cache["plan"])

    return _whole_on_each_device(aggregate, fl)


class Topology:
    """Base class for federation-topology plugins.

    Subclasses set ``name`` and implement the three responsibilities:
    ``build_round_step`` (aggregation stage), ``round_bytes``/``summary``
    (exact accounting) and ``make_mesh`` (device view).  ``stateful``
    declares that the server state is not a single global model —
    ``init_state``/``global_params`` convert between the two (identity
    for star topologies).
    """

    name: ClassVar[str] = ""
    stateful: ClassVar[bool] = False

    # -- server state -----------------------------------------------------

    def init_state(self, params: PyTree, fl) -> PyTree:
        return params

    def global_params(self, state: PyTree, fl) -> PyTree:
        return state

    # -- the compiled round ----------------------------------------------

    def build_round_step(self, loss_fn: Callable, assign: UnitAssignment,
                         fl, loss_kwargs: Optional[Dict] = None, *,
                         strategy=None, scores=None):
        raise NotImplementedError

    def build_buffered_flush(self, assign: UnitAssignment, fl):
        """The topology's buffered-async aggregation stage (DESIGN.md
        §8): ``flush(global, pdeltas, rows, valid, sel, weights,
        client_ids) -> new_global`` over a stacked ``(B, ...)`` buffer
        of packed updates — the same scatter-accumulate as the sync
        packed round, so a zero-staleness flush is bit-exact with it.
        Star topologies implement this; stateful ones (gossip) have no
        global model to buffer against.
        """
        raise ValueError(
            f"topology {self.name!r} has no buffered-async path; set "
            "FLConfig.async_buffer=0 or use hub/hierarchical")

    def build_chunk_agg(self, assign: UnitAssignment, fl):
        """The topology's chunk-streamed aggregation stage (DESIGN.md
        §13): ``(init, accumulate, finalize)`` over the packed carry
        primitives of core/aggregation.py.  ``init(global) -> acc``;
        ``accumulate(acc, pdeltas, rows, valid, weights, positions) ->
        acc`` folds one chunk of packed uploads (``positions`` are the
        chunk's cohort positions, in order); ``finalize(global, acc,
        sel, weights) -> new_global`` applies the full-cohort
        denominators.  Streaming any chunking of the cohort in order
        reproduces the single-shot packed aggregate bitwise.
        """
        raise ValueError(
            f"topology {self.name!r} has no chunked cohort path; set "
            "FLConfig.cohort_chunk=0/n_registered=0 or use "
            "hub/hierarchical")

    # -- exact byte accounting -------------------------------------------

    def round_bytes(self, sel: np.ndarray, ubytes: np.ndarray,
                    fl) -> Dict[str, float]:
        raise NotImplementedError

    def buffered_round_bytes(self, entry_sel: np.ndarray,
                             client_ids: np.ndarray, ubytes: np.ndarray,
                             fl) -> Dict[str, float]:
        """Per-flush byte math for buffered async rounds (one
        ``entry_sel`` row per buffered update)."""
        raise ValueError(
            f"topology {self.name!r} has no buffered-async accounting")

    def summary(self, assign: UnitAssignment, params: PyTree,
                sel_history: np.ndarray, fl,
                wire_ubytes: Optional[np.ndarray] = None) -> Dict[str, float]:
        """Run-level comm summary; same core keys for every topology.

        ``wire_ubytes`` (the codec-encoded per-unit byte table) bills
        the per-round uplink at wire width; the ``reduction_vs_full``
        denominator stays the fp32 full-model round, so the reported
        reduction composes the structural freeze factor with the codec's
        compression factor.
        """
        ub = comm.unit_bytes(assign, params)
        wub = ub if wire_ubytes is None else wire_ubytes
        counts = comm.unit_param_counts(assign, params)
        hist = np.asarray(sel_history)
        per_round = [self.round_bytes(s, wub, fl)["uplink"] for s in hist]
        per_round_params = np.einsum("rcu,u->r", hist, counts)
        full = self.round_bytes(np.ones_like(hist[0]), ub, fl)["uplink"]
        return {
            "avg_uplink_bytes": float(np.mean(per_round)),
            "avg_trained_params": float(per_round_params.mean()),
            "total_uplink_bytes": float(np.sum(per_round)),
            "reduction_vs_full": 1.0 - float(np.mean(per_round)) / full
            if full else 0.0,
        }

    # -- mesh view --------------------------------------------------------

    def make_mesh(self, fl, *, multi_pod: bool = False):
        from ..launch.mesh import make_fl_mesh
        return make_fl_mesh(fl.n_clients, multi_pod=multi_pod)

    def __repr__(self):
        return f"{type(self).__name__}(name={self.name!r})"


# ---------------------------------------------------------------------------
# registry (mirrors core/strategies.py)

_REGISTRY: Dict[str, Topology] = {}


class UnknownTopologyError(ValueError):
    pass


def register_topology(obj: Union[Type[Topology], Topology], *,
                      name: Optional[str] = None):
    """Register a topology class (instantiated with no args) or instance.

    Usable as a decorator::

        @register_topology
        class Mine(Topology):
            name = "mine"
            ...
    """
    topo = obj() if isinstance(obj, type) else obj
    key = name or topo.name
    if not key:
        raise ValueError(f"topology {obj!r} has no name")
    _REGISTRY[key] = topo
    return obj


def unregister_topology(name: str):
    _REGISTRY.pop(name, None)


def registered_topologies() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_topology(name: str) -> Topology:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise UnknownTopologyError(unknown_name_message(
            "topology", name, _REGISTRY)) from None


def resolve_topology(spec: Union[str, Topology, None]) -> Topology:
    """Name or instance -> instance (None -> the hub default)."""
    if spec is None:
        return get_topology("hub")
    return get_topology(spec) if isinstance(spec, str) else spec


# ---------------------------------------------------------------------------
# built-in topologies

@register_topology
class Hub(Topology):
    """The paper's FEDn combiner star: every client talks to one hub.

    The default — its round step is the identical trace the
    pre-topology ``build_round_step`` compiled (bit-exact).
    """
    name = "hub"

    def build_round_step(self, loss_fn, assign, fl, loss_kwargs=None, *,
                         strategy=None, scores=None):
        if fl.resolve_fused_agg():
            aggregate = _fused_hub_aggregate(assign, fl)
        else:
            aggregate = lambda g, d, sel, w: masked_fedavg(g, d, sel, w,
                                                           assign)
        return _star_round_step(
            loss_fn, assign, fl, loss_kwargs, strategy=strategy,
            scores=scores,
            aggregate=aggregate,
            aggregate_dense=lambda g, d, sel, w: fedavg(g, d, w),
            aggregate_packed=lambda g, d, r, v, sel, w:
                masked_fedavg_packed(g, d, r, v, sel, w, assign))

    def build_buffered_flush(self, assign, fl):
        def flush(g, pdeltas, rows, valid, sel, weights, client_ids):
            return masked_fedavg_packed(g, pdeltas, rows, valid, sel,
                                        weights, assign)
        return flush

    def build_chunk_agg(self, assign, fl):
        def init(g):
            return packed_acc_init(assign, g)

        def accumulate(acc, pdeltas, rows, valid, weights, positions):
            return packed_accumulate(assign, acc, pdeltas, rows, valid,
                                     weights)

        def finalize(g, acc, sel, weights):
            return packed_finalize(assign, g, acc, sel, weights)

        return init, accumulate, finalize

    def round_bytes(self, sel, ubytes, fl):
        return comm.hub_round_bytes(
            sel, ubytes,
            downlink="selected" if fl.synchronized else "full")

    def buffered_round_bytes(self, entry_sel, client_ids, ubytes, fl):
        return comm.buffered_hub_round_bytes(
            entry_sel, ubytes,
            downlink="selected" if fl.synchronized else "full")

    def summary(self, assign, params, sel_history, fl, wire_ubytes=None):
        # the exact Table 4 reproduction, unchanged from PR 1; a codec's
        # wire byte table rebills the uplink terms at encoded width
        return comm.table4_row(assign, params, sel_history,
                               wire_ubytes=wire_ubytes)


@register_topology
class Hierarchical(Topology):
    """Edge aggregators between clients and hub (FLConfig.n_edges).

    Clients are partitioned into contiguous edge groups; each edge
    reduces its clients' masked deltas into per-unit partial aggregates
    and only the per-edge selection union crosses the edge->hub WAN
    link — ``round_bytes`` reports that WAN term as ``uplink``.
    """
    name = "hierarchical"

    def build_round_step(self, loss_fn, assign, fl, loss_kwargs=None, *,
                         strategy=None, scores=None):
        mem = jnp.asarray(comm.edge_membership(fl.n_clients,
                                               fl.resolve_n_edges()))
        if fl.resolve_fused_agg():
            agg = _fused_hier_aggregate(assign, mem, fl)
        else:
            agg = lambda g, d, sel, w: hierarchical_masked_fedavg(
                g, d, sel, w, assign, mem)
        return _star_round_step(
            loss_fn, assign, fl, loss_kwargs, strategy=strategy,
            scores=scores, aggregate=agg, aggregate_dense=agg,
            aggregate_packed=lambda g, d, r, v, sel, w:
                hierarchical_masked_fedavg_packed(g, d, r, v, sel, w,
                                                  assign, mem))

    def build_buffered_flush(self, assign, fl):
        mem = jnp.asarray(comm.edge_membership(fl.n_clients,
                                               fl.resolve_n_edges()))

        def flush(g, pdeltas, rows, valid, sel, weights, client_ids):
            # (E, B) membership: entry j reduces at its client's edge
            return hierarchical_masked_fedavg_packed(
                g, pdeltas, rows, valid, sel, weights, assign,
                mem[:, client_ids])
        return flush

    def build_chunk_agg(self, assign, fl):
        mem = jnp.asarray(comm.edge_membership(
            fl.n_clients, fl.resolve_n_edges())).astype(jnp.float32)
        edge_of = jnp.argmax(mem, axis=0)                     # (C,)

        def init(g):
            return packed_acc_init(assign, g, n_edges=mem.shape[0])

        def accumulate(acc, pdeltas, rows, valid, weights, positions):
            # each chunk client lands in its edge's stage-1 partial
            return packed_accumulate(assign, acc, pdeltas, rows, valid,
                                     weights, edge_idx=edge_of[positions])

        def finalize(g, acc, sel, weights):
            return packed_finalize(assign, g, acc, sel, weights,
                                   membership=mem)

        return init, accumulate, finalize

    def round_bytes(self, sel, ubytes, fl):
        mem = comm.edge_membership(fl.n_clients, fl.resolve_n_edges())
        return comm.hierarchical_round_bytes(
            sel, ubytes, mem,
            downlink="selected" if fl.synchronized else "full")

    def buffered_round_bytes(self, entry_sel, client_ids, ubytes, fl):
        mem = comm.edge_membership(fl.n_clients, fl.resolve_n_edges())
        return comm.buffered_hierarchical_round_bytes(
            entry_sel, client_ids, ubytes, mem,
            downlink="selected" if fl.synchronized else "full")

    def make_mesh(self, fl, *, multi_pod: bool = False):
        from ..launch.mesh import make_hier_fl_mesh
        return make_hier_fl_mesh(fl.resolve_n_edges(), fl.n_clients,
                                 multi_pod=multi_pod)


@register_topology
class Gossip(Topology):
    """Hubless peer averaging over a doubly-stochastic ring.

    The server state is a stacked pytree of per-client replicas
    (leading C axis) carried across rounds.  Per round each client runs
    masked local training from its OWN replica, then replicas mix:
    ``x' = W @ x`` with the ring Metropolis matrix W.  W is doubly
    stochastic, so the uniform replica average — ``global_params`` — is
    exactly preserved by mixing and drifts only through local training.
    Client data weights reweight nothing here (mixing is fixed);
    zero-weight clients (stragglers) skip their local update but still
    mix.
    """
    name = "gossip"
    stateful = True

    def init_state(self, params, fl):
        c = fl.n_clients
        return jax.tree_util.tree_map(
            lambda x: jnp.tile(x[None], (c,) + (1,) * jnp.ndim(x)), params)

    def global_params(self, state, fl):
        return jax.tree_util.tree_map(
            lambda x: jnp.mean(x.astype(jnp.float32), axis=0).astype(x.dtype),
            state)

    def build_round_step(self, loss_fn, assign, fl, loss_kwargs=None, *,
                         strategy=None, scores=None):
        if fl.packed:
            raise ValueError(
                "packed round path: gossip mixing blends full replicas, "
                "so there is nothing to pack — use hub or hierarchical")
        if getattr(fl, "client_shards", 0):
            raise ValueError(
                "client_shards: gossip carries per-client replicas as "
                "server state and mixes them with a ring matmul — the "
                "cohort cannot shard over the client mesh axis; use "
                "hub or hierarchical")
        strat, ctx = _selection_setup(assign, fl, strategy, scores)
        mix = jnp.asarray(ring_mixing_matrix(fl.n_clients))
        scoring = strat.stateful

        def round_step(state, client_batches, weights, round_key,
                       sel_state=None):
            with jax.named_scope("fl/select"):
                sel = strat.select(round_key, _live_ctx(ctx, sel_state))
                if fl.always_train_head:
                    sel = sel.at[:, -1].set(1.0)
            active = (weights > 0).astype(jnp.float32)       # (C,)
            hook = dense_norm_hook(assign) if scoring else None

            def one_client(params_c, sel_row, batches):
                mask = mask_tree(assign, sel_row, params_c)
                return local_update(loss_fn, params_c, mask, batches,
                                    lr=fl.lr, optimizer=fl.optimizer,
                                    prox_mu=fl.prox_mu,
                                    loss_kwargs=loss_kwargs,
                                    norm_hook=hook)

            with jax.named_scope("fl/local_train"):
                deltas, metrics = jax.vmap(one_client)(state, sel,
                                                       client_batches)
            with jax.named_scope("fl/aggregate"):
                trained = jax.tree_util.tree_map(
                    lambda x, d: x + (d * active.reshape(
                        (-1,) + (1,) * (d.ndim - 1))).astype(x.dtype),
                    state, deltas)
                mixed = jax.tree_util.tree_map(
                    lambda x: jnp.tensordot(
                        mix, x.astype(jnp.float32), axes=(1, 0),
                        precision=F32).astype(x.dtype),
                    trained)
            with jax.named_scope("fl/finalize"):
                out_metrics = {
                    "loss_mean": metrics["loss_mean"].mean(),
                    "loss_per_client": metrics["loss_mean"],
                    "loss_steps": metrics["loss_steps"],
                    "sel": sel,
                }
            if scoring:
                out_metrics["unit_sqnorm"] = metrics["unit_sqnorm"]
            return mixed, out_metrics

        round_step.selection_strategy = strat
        return round_step

    def round_bytes(self, sel, ubytes, fl):
        return comm.gossip_round_bytes(sel, ubytes)

    def summary(self, assign, params, sel_history, fl, wire_ubytes=None):
        # codecs are rejected for gossip at config time (no packed
        # uplink), so wire_ubytes can only be the fp32 table here
        out = Topology.summary(self, assign, params, sel_history, fl,
                               wire_ubytes)
        hist = np.asarray(sel_history)
        ub = comm.unit_bytes(assign, params)
        out["degree"] = comm.gossip_round_bytes(hist[0], ub)["degree"]
        return out
