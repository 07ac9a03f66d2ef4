"""Fleet-scale cohort engine: chunk-streamed rounds over a registered
client fleet (DESIGN.md §13).

The synchronous loop materializes the whole cohort at once: one
``(C, steps, ...)`` batch pytree, one width-C vmapped local-training
trace, one dense aggregate.  That couples *how many clients exist* to
*how much memory one round takes* — a fleet of 10^5 registered edge
nodes cannot even be enumerated, let alone vmapped.  This module breaks
the coupling along three axes:

* **Registered fleet vs. in-flight cohort** (``FLConfig.n_registered``):
  the server knows R clients but trains an ``n_clients``-sized cohort
  per round.  Host state per registered client is O(1) scalars — the
  :class:`FleetState` loss/grad-norm EMAs — never a batch or a delta.
* **Chunk streaming** (``FLConfig.cohort_chunk``): the cohort flows
  through the round in fixed-size chunks, each chunk one compiled step
  (static shapes — one compile for every chunk of every round), with a
  scatter-accumulate partial aggregate carried across chunks
  (``aggregation.packed_accumulate``).  Because the packed aggregation
  is a strictly sequential per-client scan, *any* chunking in cohort
  order is **bitwise-equal** to the single-shot vmapped round
  (property-tested across topologies × strategies × chunk sizes,
  including straggler dropout and mid-round checkpoint restore).
* **Client-sampling plugin axis** (``@register_client_sampler``,
  mirroring the selection-strategy registry): which R-fleet members
  form the round's cohort.  ``uniform`` draws without replacement;
  ``loss_proportional`` and ``telemetry_driven`` Gumbel-top-k against
  the fleet's loss / gradient-norm EMAs — the same per-unit norm-hook
  telemetry the scored selection engine reads (DESIGN.md §11), reduced
  per client and EMA'd per fleet member.

Sampler keys come off their own stateless stream
(``fold_in(sampler_base, round)``), NOT the server key stream — so with
R == C and any sampler the cohort is the identity and the engine's
rounds are bitwise the plain loop's (the regression anchor), and a
checkpoint needs no sampler RNG state.

The engine mirrors ``Server.run_round``'s observable contract exactly —
same key-stream order (round key drawn before hooks), same hook
call points, same ``RoundRecord``/``sel_history``/telemetry layout — so
every ``ServerHook`` (straggler dropout, accounting, checkpointing,
logging) composes unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, ClassVar, Dict, List, Optional, Tuple, \
    Type, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.compileguard import CompileGuard
from ..common import spans
from .registry import unknown_name_message

PyTree = Any


# ---------------------------------------------------------------------------
# fleet state: O(1) host scalars per registered client

@dataclasses.dataclass
class FleetState:
    """Per-registered-client signals the samplers read.

    All ``(R,)`` numpy arrays — the ONLY per-registered-client host
    state the engine keeps (batches and deltas exist per cohort chunk
    only), which is what bounds host memory at fleet scale.
    """
    loss_ema: np.ndarray      # (R,) EMA of the client's round mean loss
    norm_ema: np.ndarray      # (R,) EMA of the client's total grad norm
    counts: np.ndarray        # (R,) participation counts (0 = unseen)
    round: int = 0            # rounds the fleet has advanced through


def fleet_init(n_registered: int) -> FleetState:
    return FleetState(loss_ema=np.zeros((n_registered,), np.float32),
                      norm_ema=np.zeros((n_registered,), np.float32),
                      counts=np.zeros((n_registered,), np.int32))


@dataclasses.dataclass(frozen=True)
class CohortContext:
    """What a sampler sees when drawing a round's cohort."""
    n_registered: int
    cohort: int
    fleet: FleetState


# ---------------------------------------------------------------------------
# client-sampler registry (mirrors strategies/topologies)

class ClientSampler:
    """Base class for cohort-sampling plugins.

    ``sample(key, ctx)`` returns the round's cohort as a **sorted**
    ``(cohort,)`` array of unique registered-client ids.  Sorted order
    is load-bearing: with R == C every sampler then returns
    ``arange(C)`` and the engine's rounds are bitwise the plain loop's.
    ``needs_norms`` turns the per-unit gradient-norm hook on inside
    local training so :class:`FleetState.norm_ema` gets fed.
    """

    name: ClassVar[str] = ""
    needs_norms: ClassVar[bool] = False

    def sample(self, key, ctx: CohortContext) -> np.ndarray:
        raise NotImplementedError

    def __repr__(self):
        return f"{type(self).__name__}(name={self.name!r})"


_SAMPLERS: Dict[str, ClientSampler] = {}


class UnknownClientSamplerError(ValueError):
    pass


def register_client_sampler(obj: Union[Type[ClientSampler], ClientSampler],
                            *, name: Optional[str] = None):
    """Register a sampler class (instantiated with no args) or instance.

    Usable as a decorator::

        @register_client_sampler
        class Mine(ClientSampler):
            name = "mine"
            ...
    """
    sampler = obj() if isinstance(obj, type) else obj
    key = name or sampler.name
    if not key:
        raise ValueError(f"client sampler {obj!r} has no name")
    _SAMPLERS[key] = sampler
    return obj


def unregister_client_sampler(name: str):
    _SAMPLERS.pop(name, None)


def registered_client_samplers() -> Tuple[str, ...]:
    return tuple(sorted(_SAMPLERS))


def get_client_sampler(name: str) -> ClientSampler:
    try:
        return _SAMPLERS[name]
    except KeyError:
        raise UnknownClientSamplerError(unknown_name_message(
            "client sampler", name, _SAMPLERS)) from None


def resolve_client_sampler(spec: Union[str, ClientSampler, None]
                           ) -> ClientSampler:
    """Name or instance -> instance (None -> the uniform default)."""
    if spec is None:
        return get_client_sampler("uniform")
    return get_client_sampler(spec) if isinstance(spec, str) else spec


def _uniform_draw(key, n_registered: int, cohort: int) -> np.ndarray:
    """O(cohort) uniform draw without replacement (Floyd's algorithm).

    The previous implementation materialized ``permutation(key, R)`` —
    O(R) memory and O(R log R) device work per round, untenable at
    R = 10^6 registered clients when only C of them train.  Floyd's F2
    touches exactly ``cohort`` draws: for j in R-C..R-1 pick t uniform
    on [0, j], take t unless already taken, else take j.  Exactly
    uniform over C-subsets, O(C) time and memory, independent of R.

    Seed contract (bitwise-stable; pinned by the regression suite): the
    JAX key's raw ``key_data`` words plus the tag ``0xF107D`` seed a
    numpy ``SeedSequence`` driving a ``Philox`` generator, whose
    integer stream is specified and platform-independent — the draw is
    a pure function of the key bits, so the engine still needs no
    sampler RNG state in checkpoints.  With cohort == n_registered the
    draw is the identity ``arange`` (the R == C bitwise anchor).
    """
    if cohort >= n_registered:
        return np.arange(n_registered, dtype=np.int32)
    words = [int(w) for w in np.asarray(
        spans.fetch(jax.random.key_data(key), "sample"), np.uint32).ravel()]
    rng = np.random.Generator(np.random.Philox(
        np.random.SeedSequence(words + [0xF107D])))
    # vectorized pre-draw: t_j ~ U[0, j] for j = R-C .. R-1
    ts = rng.integers(0, np.arange(n_registered - cohort,
                                   n_registered) + 1)
    chosen: set = set()
    for j, t in zip(range(n_registered - cohort, n_registered), ts):
        t = int(t)
        chosen.add(j if t in chosen else t)
    return np.asarray(sorted(chosen), np.int32)


def _scored_draw(key, signal: np.ndarray, seen: np.ndarray,
                 cohort: int) -> np.ndarray:
    """Gumbel-top-k draw ∝ softmax of the z-scored signal.

    Unseen clients take the *maximum* seen signal (optimistic
    initialization: every fleet member gets sampled eventually), and
    with no signal at all the draw degrades to uniform on the same key.
    """
    if not seen.any():
        return _uniform_draw(key, signal.shape[0], cohort)
    s = np.where(seen, signal, signal[seen].max()).astype(np.float64)
    z = (s - s.mean()) / (s.std() + 1e-6)
    g = np.asarray(spans.fetch(jax.random.gumbel(key, s.shape), "sample"),
                   np.float64)
    top = np.argsort(-(z + g), kind="stable")[:cohort]
    return np.sort(top).astype(np.int32)


@register_client_sampler
class UniformSampler(ClientSampler):
    """Uniform without replacement — the FedAvg default."""
    name = "uniform"

    def sample(self, key, ctx):
        return _uniform_draw(key, ctx.n_registered, ctx.cohort)


@register_client_sampler
class LossProportionalSampler(ClientSampler):
    """Prefer clients whose recent loss EMA is high (they have the most
    to learn from another round)."""
    name = "loss_proportional"

    def sample(self, key, ctx):
        return _scored_draw(key, ctx.fleet.loss_ema,
                            ctx.fleet.counts > 0, ctx.cohort)


@register_client_sampler
class TelemetryDrivenSampler(ClientSampler):
    """Prefer clients whose gradient-norm EMA is high — the fleet-level
    analogue of score-weighted unit selection (DESIGN.md §11), fed by
    the same norm-hook telemetry."""
    name = "telemetry_driven"
    needs_norms = True

    def sample(self, key, ctx):
        return _scored_draw(key, ctx.fleet.norm_ema,
                            ctx.fleet.counts > 0, ctx.cohort)


# ---------------------------------------------------------------------------
# compiled programs

@dataclasses.dataclass
class CohortPrograms:
    """The engine's four compiled pieces plus resolved plugins.

    * ``select(key[, sel_state]) -> sel (C, U)`` — the round's
      per-client trained-unit selection (bitwise the sync round's);
    * ``acc_init(global) -> acc`` — the zero partial aggregate;
    * ``chunk(global, acc, sel_chunk, w_chunk, positions, batches) ->
      (acc, {"loss"[, "unit_sqnorm"]})`` — one chunk's packed local
      training folded into the carry (static shapes: one compile
      serves every chunk of every round);
    * ``finalize(global, acc, sel, weights, losses) -> (new_global,
      loss_mean)`` — full-cohort denominators + the round loss.
    """
    select: Callable
    acc_init: Callable
    chunk: Callable
    finalize: Callable
    sampler: ClientSampler
    strategy: Any
    scoring: bool
    n_slots: int


def build_cohort_programs(loss_fn: Callable, assign, fl,
                          loss_kwargs: Optional[Dict] = None, *,
                          strategy=None, scores=None,
                          topology=None) -> CohortPrograms:
    """Build the chunk-streamed round's compiled programs.

    The chunk program is the sync packed round step's selection +
    vmapped packed local training (``client.packed_cohort_fn`` — the
    same trace, optionally shard_map'd over the ``(client,)`` mesh via
    ``fl.client_shards``) followed by ``Topology.build_chunk_agg``'s
    scatter-accumulate.  Streaming every chunk and finalizing is
    bitwise the single-shot ``masked_fedavg_packed`` by construction:
    the accumulate is a sequential per-client scan, and splitting a
    scan across calls changes nothing about its float-add order.
    """
    from .client import packed_cohort_fn
    from .masking import slot_plan
    from .topology import (_cohort_runner, _live_ctx, _selection_setup,
                           resolve_topology)
    from . import codecs as _codecs
    from . import faults as _faults
    from .aggregation import gate_packed_updates
    topo = resolve_topology(topology if topology is not None
                            else fl.topology)
    strat, ctx = _selection_setup(assign, fl, strategy, scores)
    if strat.dense:
        raise ValueError(
            "the chunked cohort engine carries packed trained-slot "
            "deltas; the dense 'full' strategy has nothing to pack — "
            "use a partial strategy (train_fraction < 1)")
    sampler = resolve_client_sampler(fl.client_sampler)
    n_slots = fl.resolve_n_slots(ctx.n_units)
    scoring = strat.stateful or sampler.needs_norms
    acc_init, accumulate, finalize_agg = topo.build_chunk_agg(assign, fl)
    chunk_width = fl.cohort_chunk or fl.n_clients
    run_cohort = _cohort_runner(fl, chunk_width)
    cohort = packed_cohort_fn(loss_fn, assign, fl, loss_kwargs,
                              scoring=scoring)

    # each stage under its scope (fl/<stage>), as in the sync round step
    @jax.named_scope("fl/select")
    def select(key, sel_state=None):
        sel = strat.select(key, _live_ctx(ctx, sel_state))
        if fl.always_train_head:
            sel = sel.at[:, -1].set(1.0)
        return sel

    # the zero partial aggregate (the program keeps its function's name)
    acc_init = jax.named_scope("fl/aggregate")(acc_init)

    inject_on = _faults.delta_faults_configured(fl)
    gate_on = _faults.gate_enabled(fl)
    codec_fn = _codecs.build_codec_transform(
        _codecs.resolve_codec(fl.codec), assign, fl)

    def chunk_step(global_params, acc, sel_chunk, w_chunk, positions,
                   batches, mode=None, scale=None, codec_key=None):
        with jax.named_scope("fl/slot_plan"):
            rows, valid = jax.vmap(
                lambda s: slot_plan(assign, s, n_slots, global_params)
            )(sel_chunk)
        with jax.named_scope("fl/local_train"):
            pdeltas, metrics = run_cohort(cohort, global_params, rows,
                                          valid, batches)
        out = {"loss": metrics["loss_mean"],
               "loss_steps": metrics["loss_steps"]}
        if scoring:
            out["unit_sqnorm"] = metrics["unit_sqnorm"]
        # uplink codec (DESIGN.md §16): encode/decode compiles into the
        # chunk program between local training and the fault axis, so
        # wire corruption hits what actually crossed the WAN.  Only
        # stateless codecs reach this path — FLConfig rejects the
        # error-feedback codec × cohort engine combination up front.
        if codec_fn is not None:
            with jax.named_scope("fl/codec"):
                pdeltas, _ = codec_fn(pdeltas, rows, valid, w_chunk,
                                      codec_key)
        # fault axis (DESIGN.md §14): corruption + validation gate ride
        # the chunk program when configured — both bitwise identities
        # when untripped, so zero-rate chaos keeps chunked == single-
        # shot == plain bitwise
        if inject_on:
            with jax.named_scope("fl/faults"):
                if mode is None:
                    mode = jnp.zeros((chunk_width,), jnp.int32)
                    scale = jnp.ones((chunk_width,), jnp.float32)
                pdeltas = _faults.chaos_inject(pdeltas, mode, scale)
        if gate_on:
            with jax.named_scope("fl/gate"):
                pdeltas, w_chunk, quar = gate_packed_updates(
                    assign, pdeltas, valid, w_chunk, fl.max_delta_norm)
            out["quarantined"] = quar
        with jax.named_scope("fl/aggregate"):
            acc = accumulate(acc, pdeltas, rows, valid, w_chunk, positions)
        return acc, out

    @jax.named_scope("fl/finalize")
    def finalize(global_params, acc, sel, weights, losses):
        new_params = finalize_agg(global_params, acc, sel, weights)
        # same jnp.mean over the same (C,) values the sync round step
        # reduces, so the recorded loss is bitwise the sync round's
        return new_params, losses.mean()

    # chunk and finalize donate the ``acc`` carry (argnum 1): the
    # engine reassigns p["acc"] from every chunk's output and discards
    # it after finalize, so each chunk scatter-accumulates into the
    # donated buffer instead of allocating a fresh partial aggregate
    # per chunk.  global_params is NOT donated — it is re-read by every
    # chunk of the round.  CompileGuard pins each program to exactly
    # one compile across the round's chunks (and across rounds).
    return CohortPrograms(
        select=CompileGuard(select, name="cohort_select", max_programs=1),
        acc_init=CompileGuard(acc_init, name="cohort_acc_init",
                              max_programs=1),
        chunk=CompileGuard(chunk_step, name="cohort_chunk",
                           max_programs=1, donate_argnums=(1,)),
        finalize=CompileGuard(finalize, name="cohort_finalize",
                              max_programs=1, donate_argnums=(1,)),
        sampler=sampler, strategy=strat, scoring=scoring, n_slots=n_slots)


# ---------------------------------------------------------------------------
# the engine

class CohortEngine:
    """Drives chunk-streamed rounds over a registered fleet.

    A round is three phases — ``begin_round`` (sample the cohort, draw
    the selection, zero the partial aggregate), ``step_chunk`` × the
    chunk count (stream one chunk's batches through packed local
    training into the carry), ``finish_round`` (full-cohort finalize,
    record, telemetry, fleet EMAs) — composed by ``run_round``/``run``.
    Host memory in flight is O(chunk) batches + O(cohort) selection
    rows + one packed accumulator, regardless of R.

    ``batch_fn(round_idx, client_ids) -> (len(ids), steps, ...)``
    pytree is the loader contract (``FederatedLoader.client_batches``):
    the host never materializes more than one chunk of batches.

    Checkpointing: ``checkpoint_state``/``restore_state`` carry the
    fleet EMAs and — mid-round — the partial aggregate, streamed-chunk
    counter, cohort ids/selection/weights and per-chunk losses, so a
    restore at any chunk boundary resumes bitwise (the server key
    stream was already advanced by ``begin_round`` and is saved by the
    ordinary server checkpoint).
    """

    def __init__(self, server, assign, fl, *, programs: CohortPrograms,
                 seed: int = 0):
        self.server = server
        self.assign = assign
        self.fl = fl
        self.programs = programs
        self.n_registered = fl.n_registered or fl.n_clients
        self.chunk = fl.cohort_chunk or fl.n_clients
        self.n_chunks = fl.n_clients // self.chunk
        self.fleet = fleet_init(self.n_registered)
        # stateless sampler key stream: round r's draw is a pure
        # function of (seed, r), independent of the server stream —
        # nothing to checkpoint, and the server stream stays bitwise
        # identical to the plain loop's
        self._sampler_base = jax.random.fold_in(
            jax.random.PRNGKey(seed), 0x0C0F0E)
        # stateless codec key stream mirroring the sampler stream: the
        # chunk at (round r, chunk j) encodes under a pure function of
        # (seed, r, j) — nothing to checkpoint, and codec "none" never
        # draws so plain-loop key streams stay bitwise identical
        from . import codecs as _codecs
        self._codec_base = jax.random.fold_in(
            jax.random.PRNGKey(seed), _codecs.CODEC_KEY_TAG)
        self._partial: Optional[Dict[str, Any]] = None

    @property
    def started(self) -> bool:
        return self.fleet.round > 0 or self._partial is not None

    # -- the three phases -------------------------------------------------

    def begin_round(self, weights=None) -> Dict[str, Any]:
        server = self.server
        if self._partial is not None:
            raise RuntimeError(
                "a cohort round is already in flight; stream its "
                "remaining chunks and finish_round() first")
        r = len(server.history)
        rspan = spans.round_span(r)
        try:
            self._partial = self._begin(r, rspan, weights)
        except BaseException:
            rspan.close()
            raise
        return self._partial

    def _begin(self, r: int, rspan, weights) -> Dict[str, Any]:
        server = self.server
        # SAME key-stream slot as Server.run_round: round key first,
        # then hooks (StragglerDropout) draw — bitwise-equal streams
        rk = server.next_key()
        sk = jax.random.fold_in(self._sampler_base, r)
        ids = np.asarray(self.programs.sampler.sample(
            sk, CohortContext(self.n_registered, self.fl.n_clients,
                              self.fleet)), np.int32)
        # crash-resilient cohort assembly (DESIGN.md §14): crashed
        # members are resampled from the rest of the registered fleet
        # with bounded jittered backoff; slots that exhaust their
        # retries degrade to zero-weight holes (partial round)
        dead: List[int] = []
        inj = server.fault_injector
        if inj is not None and inj.crash_prob > 0.0:
            ids, dead = self._resample_crashed(r, ids)
        c = self.fl.n_clients
        if weights is None:
            w = jnp.ones((c,), jnp.float32)
        else:
            wr = np.asarray(weights, np.float32)
            if wr.shape[0] == c:
                w = jnp.asarray(wr)
            elif wr.shape[0] == self.n_registered:
                w = jnp.asarray(wr[ids])    # fleet weights -> cohort view
            else:
                raise ValueError(
                    f"weights must have length n_clients={c} (cohort) or "
                    f"n_registered={self.n_registered} (fleet), got "
                    f"{wr.shape[0]}")
        if dead:
            mask = np.ones((c,), np.float32)
            mask[dead] = 0.0
            w = w * jnp.asarray(mask)
        with spans.span("fl.hooks", phase="start"):
            for hook in server.hooks:
                new_w = hook.on_round_start(server, r, w)
                if new_w is not None:
                    w = new_w
        w_np = np.asarray(spans.fetch(w, "weights"), np.float32)
        n_part = int(np.count_nonzero(w_np))
        p: Dict[str, Any] = {
            "round": r, "span": rspan, "ids": ids, "w": jnp.asarray(w_np),
            "eff_w": [float(x) for x in w_np], "n_part": n_part,
            "chunk": 0, "losses": [], "loss_steps": [], "sqnorms": [],
            "quars": [], "skipped": n_part == 0, "sel": None, "acc": None,
        }
        if n_part:
            st = server.sel_state
            sel = spans.dispatch(self.programs.select, rk) if st is None \
                else spans.dispatch(self.programs.select, rk, st)
            p["sel"] = sel
            p["acc"] = spans.dispatch(self.programs.acc_init,
                                      server.global_params())
            if getattr(self.fl, "client_shards", 0):
                # the sharded chunk program commits its acc output to
                # the (client,) mesh; the fresh accumulator must start
                # there too or chunk 2 retraces on the sharding flip
                from jax.sharding import NamedSharding, PartitionSpec
                from ..launch.mesh import make_client_mesh
                p["acc"] = jax.device_put(
                    p["acc"],
                    NamedSharding(make_client_mesh(self.fl.client_shards),
                                  PartitionSpec()))
        return p

    def _resample_crashed(self, r: int,
                          ids: np.ndarray) -> Tuple[np.ndarray, List[int]]:
        """Replace crashed cohort members with freshly sampled fleet
        clients (bounded attempts via ``common/retry.py``); returns the
        repaired ids and the positions that stayed dead."""
        from ..common.retry import Backoff, retry_call
        from .faults import ClientCrashed
        inj = self.server.fault_injector
        ids = np.array(ids, np.int32)
        taken = {int(i) for i in ids}
        dead: List[int] = []
        backoff = Backoff(attempts=max(1, self.fl.fault_retries),
                          seed=inj.seed)
        for pos in range(ids.shape[0]):
            if not inj.crashed(r, int(ids[pos])):
                continue
            taken.discard(int(ids[pos]))

            def attempt(k, _pos=pos):
                cand = inj.resample(r, _pos, k, self.n_registered,
                                    frozenset(taken))
                if cand is None or inj.crashed(r, cand):
                    raise ClientCrashed(
                        f"round {r} slot {_pos}: no live replacement "
                        f"on attempt {k}")
                return cand

            try:
                # simulated time: the jittered backoff schedule bounds
                # attempts but nobody really sleeps (sleep=None)
                new = retry_call(attempt, backoff=backoff,
                                 retry_on=(ClientCrashed,),
                                 token=(r, pos), sleep=None)
                ids[pos] = new
                taken.add(int(new))
            except ClientCrashed:
                dead.append(pos)
        return ids, dead

    def step_chunk(self, batch_fn: Callable[[int, np.ndarray], Any]):
        p = self._partial
        if p is None:
            raise RuntimeError("no cohort round in flight; begin_round "
                               "first")
        if p["skipped"]:
            return
        j = p["chunk"]
        if j >= self.n_chunks:
            raise RuntimeError(
                f"all {self.n_chunks} chunks of round {p['round']} are "
                "already streamed; finish_round()")
        with spans.span("fl.chunk", chunk=j):
            self._step_chunk(p, j, batch_fn)

    def _step_chunk(self, p: Dict[str, Any], j: int,
                    batch_fn: Callable[[int, np.ndarray], Any]) -> None:
        lo, hi = j * self.chunk, (j + 1) * self.chunk
        pos = np.arange(lo, hi)
        batches = batch_fn(p["round"], p["ids"][pos])
        inj = self.server.fault_injector
        chunk_kw = {}
        if inj is not None and inj.has_delta:
            # the corruption plan is a pure function of (seed, round,
            # client id) — recomputed here, never checkpointed
            plan = inj.corrupt_plan(p["round"], p["ids"][pos])
            chunk_kw = {"mode": jnp.asarray(plan["mode"]),
                        "scale": jnp.asarray(plan["scale"])}
        if getattr(self.fl, "codec", "none") != "none":
            chunk_kw["codec_key"] = jax.random.fold_in(
                jax.random.fold_in(self._codec_base, p["round"]), j)
        acc, mets = spans.dispatch(
            self.programs.chunk, self.server.global_params(), p["acc"],
            p["sel"][lo:hi], p["w"][lo:hi], jnp.asarray(pos, jnp.int32),
            batches, **chunk_kw)
        p["acc"] = acc
        p["losses"].append(np.asarray(spans.fetch(mets["loss"], "loss"),
                                      np.float32))
        # kept on the device: nothing on the host reads it per chunk
        p["loss_steps"].append(mets["loss_steps"])
        if "unit_sqnorm" in mets:
            p["sqnorms"].append(np.asarray(
                spans.fetch(mets["unit_sqnorm"], "sqnorm"), np.float32))
        if "quarantined" in mets:
            p["quars"].append(np.asarray(
                spans.fetch(mets["quarantined"], "quarantined"), np.float32))
        p["chunk"] = j + 1

    def finish_round(self):
        p = self._partial
        if p is None:
            raise RuntimeError("no cohort round in flight; begin_round "
                               "first")
        if not p["skipped"] and p["chunk"] != self.n_chunks:
            raise RuntimeError(
                f"round {p['round']} has streamed {p['chunk']}/"
                f"{self.n_chunks} chunks; step_chunk the rest first")
        with p["span"] as rspan:
            rec = self._finish(p, rspan)
        self.server.history.append(rec)
        self.server._trim_history()
        return rec

    def _finish(self, p: Dict[str, Any], rspan):
        from .server import RoundRecord
        server = self.server
        r = p["round"]
        c = self.fl.n_clients
        if p["skipped"]:
            # loss 0.0 (NOT NaN): a skipped round must never leak NaN
            # into loss summaries / EMA consumers downstream
            rec = RoundRecord(r, 0.0, None, rspan.elapsed(), 0.0, 0.0,
                              n_participants=0, skipped=True,
                              dropped=True, effective_weights=p["eff_w"])
            server.sel_history.append(
                np.zeros((c, self.assign.n_units), np.float32))
            metrics = None
        else:
            losses = jnp.concatenate(
                [jnp.asarray(x) for x in p["losses"]]) \
                if len(p["losses"]) > 1 else jnp.asarray(p["losses"][0])
            w_fin = p["w"]
            quar_full = None
            if p["quars"]:
                quar_full = np.concatenate(p["quars"])
                # quarantined clients already accumulated with weight 0
                # per chunk; zero them in the full-cohort denominator too
                w_fin = w_fin * jnp.asarray(1.0 - quar_full)
            new_params, loss_mean = spans.dispatch(
                self.programs.finalize, server.global_params(), p["acc"],
                p["sel"], w_fin, losses)
            server.params = new_params   # star topologies: state==params
            server.sel_history.append(spans.fetch(p["sel"], "sel"))
            metrics = {"loss_mean": loss_mean, "loss_per_client": losses,
                       "sel": p["sel"]}
            # every chunk's, unless a restore began the round midway
            if len(p["loss_steps"]) == self.n_chunks:
                metrics["loss_steps"] = jnp.concatenate(p["loss_steps"])
            if p["sqnorms"]:
                metrics["unit_sqnorm"] = np.concatenate(p["sqnorms"],
                                                        axis=0)
            if quar_full is not None:
                metrics["quarantined"] = quar_full
            ev = None
            if server.eval_fn is not None:
                ev = float(spans.fetch(server.eval_fn(server.global_params()),
                                       "eval"))
            rec = RoundRecord(r, float(spans.fetch(loss_mean, "loss")), ev,
                              rspan.elapsed(), 0.0, 0.0,
                              n_participants=p["n_part"],
                              effective_weights=p["eff_w"])
        with spans.span("fl.hooks", phase="end"):
            # selection-state telemetry BEFORE end-of-round hooks,
            # exactly like the sync loop (a Checkpointer hook must save
            # post-round state for bit-exact mid-fit resume)
            server.update_sel_state(server._round_telemetry(r, metrics,
                                                            p["eff_w"]))
            self._update_fleet(p, metrics)
            # clear the in-flight round BEFORE end hooks: a ChaosHook kill
            # must not leave a completed round marked partial, or the
            # resumed run would double-apply it
            self._partial = None
            for hook in server.hooks:
                hook.on_round_end(server, rec, metrics)
        rec.seconds = rspan.close()
        return rec

    def _update_fleet(self, p: Dict[str, Any],
                      metrics: Optional[Dict]) -> None:
        """Fold the round into the fleet EMAs at the *sampled* ids.
        Dropped clients (effective weight 0) contributed nothing and
        update nothing, matching the aggregation and sel-state rules."""
        f = self.fleet
        if metrics is not None:
            active = np.asarray(p["eff_w"], np.float32) > 0
            if "quarantined" in metrics:
                # a quarantined upload contributed nothing to the model;
                # its (possibly poisoned) telemetry must not steer the
                # sampler either
                active &= np.asarray(metrics["quarantined"],
                                     np.float32) <= 0
            act = p["ids"][active]
            if act.size:
                e = self.fl.sampler_ema
                seen = f.counts[act] > 0
                loss = np.asarray(spans.fetch(metrics["loss_per_client"],
                                              "loss_per_client"),
                                  np.float32)[active]
                f.loss_ema[act] = np.where(
                    seen, e * f.loss_ema[act] + (1.0 - e) * loss, loss)
                if "unit_sqnorm" in metrics:
                    norm = np.asarray(metrics["unit_sqnorm"],
                                      np.float32)[active].sum(axis=1)
                    f.norm_ema[act] = np.where(
                        seen, e * f.norm_ema[act] + (1.0 - e) * norm,
                        norm)
                f.counts[act] += 1
        f.round += 1

    # -- composed loops ---------------------------------------------------

    def run_round(self, batch_fn: Callable[[int, np.ndarray], Any],
                  weights=None):
        """One full round; resumes a restored mid-round partial (whose
        hooks and key draws already happened) instead of re-beginning."""
        if self._partial is None:
            self.begin_round(weights)
        p = self._partial
        while not p["skipped"] and p["chunk"] < self.n_chunks:
            self.step_chunk(batch_fn)
        return self.finish_round()

    def run(self, rounds: int, batch_fn: Callable[[int, np.ndarray], Any],
            weights=None, log_every: int = 0):
        from .server import RoundLogger
        server = self.server
        extra = [RoundLogger(log_every,
                             total=len(server.history) + rounds,
                             base=len(server.history))] if log_every else []
        server.hooks.extend(extra)
        try:
            for _ in range(rounds):
                self.run_round(batch_fn, weights)
        finally:
            for h in extra:
                server.hooks.remove(h)
        for hook in server.hooks:
            hook.on_fit_end(server, server.history)
        return server.history

    # -- checkpoint state (ckpt/store.py) ---------------------------------

    def checkpoint_state(self) -> Tuple[Dict[str, Any], PyTree]:
        """(json metadata, array pytree): fleet EMAs always, plus the
        in-flight round's carry when saving at a chunk boundary."""
        meta: Dict[str, Any] = {
            "fleet_round": int(self.fleet.round),
            "n_registered": int(self.n_registered),
        }
        arrays: Dict[str, Any] = {"fleet": {
            "loss_ema": self.fleet.loss_ema,
            "norm_ema": self.fleet.norm_ema,
            "counts": self.fleet.counts,
        }}
        p = self._partial
        if p is not None:
            meta["partial"] = {
                "round": int(p["round"]), "chunk": int(p["chunk"]),
                "n_part": int(p["n_part"]),
                "eff_w": [float(x) for x in p["eff_w"]],
                "skipped": bool(p["skipped"]),
                "scored": bool(self.programs.scoring),
                "gated": bool(p["quars"]),
            }
            pa: Dict[str, Any] = {
                "ids": np.asarray(p["ids"], np.int32),
                "w": np.asarray(p["w"], np.float32),
            }
            if not p["skipped"]:
                pa["sel"] = np.asarray(p["sel"], np.float32)
                pa["acc"] = jax.tree_util.tree_map(np.asarray, p["acc"])
                if p["losses"]:
                    pa["losses"] = np.concatenate(p["losses"])
                if p["sqnorms"]:
                    pa["sqnorm"] = np.concatenate(p["sqnorms"], axis=0)
                if p["quars"]:
                    pa["quar"] = np.concatenate(p["quars"])
            arrays["partial"] = pa
        return meta, arrays

    def arrays_template(self, meta: Dict[str, Any]) -> PyTree:
        sds = jax.ShapeDtypeStruct
        n_r = int(meta["n_registered"])
        tpl: Dict[str, Any] = {"fleet": {
            "loss_ema": sds((n_r,), jnp.float32),
            "norm_ema": sds((n_r,), jnp.float32),
            "counts": sds((n_r,), jnp.int32),
        }}
        pm = meta.get("partial")
        if pm is not None:
            c = self.fl.n_clients
            pa: Dict[str, Any] = {"ids": sds((c,), jnp.int32),
                                  "w": sds((c,), jnp.float32)}
            if not pm["skipped"]:
                pa["sel"] = sds((c, self.assign.n_units), jnp.float32)
                pa["acc"] = jax.eval_shape(self.programs.acc_init,
                                           self.server.global_params())
                done = int(pm["chunk"]) * self.chunk
                if done:
                    pa["losses"] = sds((done,), jnp.float32)
                    if pm.get("scored"):
                        pa["sqnorm"] = sds((done, self.assign.n_units),
                                           jnp.float32)
                    if pm.get("gated"):
                        pa["quar"] = sds((done,), jnp.float32)
            tpl["partial"] = pa
        return tpl

    def restore_state(self, meta: Dict[str, Any], arrays: PyTree):
        if int(meta["n_registered"]) != self.n_registered:
            raise ValueError(
                f"checkpoint fleet has {meta['n_registered']} registered "
                f"clients, this engine {self.n_registered}; restore with "
                "the original FLConfig.n_registered")
        fa = arrays["fleet"]
        # np.array (copy): views of jnp arrays are read-only, and the
        # fleet EMAs are updated in place every round
        self.fleet = FleetState(
            loss_ema=np.array(fa["loss_ema"], np.float32),
            norm_ema=np.array(fa["norm_ema"], np.float32),
            counts=np.array(fa["counts"], np.int32),
            round=int(meta["fleet_round"]))
        pm = meta.get("partial")
        if pm is None:
            self._partial = None
            return
        pa = arrays["partial"]
        p: Dict[str, Any] = {
            "round": int(pm["round"]),
            "span": spans.round_span(int(pm["round"])),
            "ids": np.asarray(pa["ids"], np.int32),
            "w": jnp.asarray(np.asarray(pa["w"], np.float32)),
            "eff_w": [float(x) for x in pm["eff_w"]],
            "n_part": int(pm["n_part"]), "chunk": int(pm["chunk"]),
            "skipped": bool(pm["skipped"]),
            "losses": [], "loss_steps": [], "sqnorms": [], "quars": [],
            "sel": None, "acc": None,
        }
        if not p["skipped"]:
            p["sel"] = jnp.asarray(np.asarray(pa["sel"], np.float32))
            p["acc"] = jax.tree_util.tree_map(jnp.asarray, pa["acc"])
            if "losses" in pa:
                p["losses"] = [np.asarray(pa["losses"], np.float32)]
            if "sqnorm" in pa:
                p["sqnorms"] = [np.asarray(pa["sqnorm"], np.float32)]
            if "quar" in pa:
                p["quars"] = [np.asarray(pa["quar"], np.float32)]
        self._partial = p
