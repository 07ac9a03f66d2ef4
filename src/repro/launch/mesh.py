"""Production meshes (TPU v5e target) and FL logical views.

``make_production_mesh`` is the spec-literal mesh: (16, 16)
("data", "model") for one 256-chip pod; (2, 16, 16)
("pod", "data", "model") for the 2-pod, 512-chip deployment.

``make_fl_mesh`` is the federated *view* of the same device array
(DESIGN.md §5): a leading ``client`` axis carved out of the data axis —
clients are mesh subgroups (cross-device mode) or whole pods (cross-silo
mode, multi-pod: clients never span a pod, so the pod axis folds into
the client axis and the paper's WAN bottleneck lands on the pod-to-pod
DCN link).

``make_hier_fl_mesh`` is the hierarchical topology's view (DESIGN.md
§6): the client axis further carved into a leading ``edge`` group axis,
``(edge, client, data, model)``.  Clients of one edge are adjacent mesh
subgroups (their reduce stays on local interconnect — the edge
aggregator); only the per-edge partial aggregates cross the ``edge``
axis boundary, which is the edge->hub WAN link.

``make_client_mesh`` / ``client_split`` / ``shard_over_clients`` are the
cohort engine's 1-D ``(client,)`` device mesh (DESIGN.md §13): the
in-flight cohort's leading client axis is split over device groups with
``shard_map``, each group vmapping its shard of clients — per-client
rows of a batched local update are independent of their cohort, so the
sharded run is bitwise-equal to the single-device vmap (property-tested).

Functions, not module constants: importing this module never touches
jax device state (dryrun.py must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def _nearest_valid(total: int, want: int) -> str:
    """Human hint: the divisors of ``total`` bracketing ``want``."""
    divs = [d for d in range(1, total + 1) if total % d == 0]
    below = max((d for d in divs if d < want), default=None)
    above = min((d for d in divs if d > want), default=None)
    opts = [str(d) for d in (below, above) if d is not None]
    return " or ".join(opts) if opts else "none"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         devices=jax.devices()[: _size(shape)])


def make_fl_mesh(n_clients: int, *, multi_pod: bool = False):
    """(client, data, model) view with client*data = pods*16, model = 16."""
    pods = 2 if multi_pod else 1
    total_dp = pods * 16
    if multi_pod:
        # cross-silo: the pod axis folds into the client axis
        n_clients = max(n_clients, pods)
        if n_clients % pods:
            raise ValueError(
                f"multi-pod clients must fill the {pods} pods evenly: "
                f"requested {n_clients} clients with "
                f"{len(jax.devices())} devices visible; nearest valid "
                f"cohort sizes: {pods * (n_clients // pods)} or "
                f"{pods * (n_clients // pods + 1)}")
    if total_dp % n_clients:
        raise ValueError(
            f"client axis {n_clients} must divide the {total_dp}-way "
            f"data parallelism ({len(jax.devices())} devices visible, "
            f"model axis 16); nearest valid cohort sizes: "
            f"{_nearest_valid(total_dp, n_clients)}")
    shape = (n_clients, total_dp // n_clients, 16)
    return jax.make_mesh(shape, ("client", "data", "model"),
                         devices=jax.devices()[: _size(shape)])


def make_hier_fl_mesh(n_edges: int, n_clients: int, *,
                      multi_pod: bool = False):
    """(edge, client, data, model) view: edge * client * data = DP chips.

    The flat client axis of ``make_fl_mesh`` is split edge-major, so
    client c lands in edge c // (n_clients/n_edges) — matching the
    contiguous edge groups the hierarchical aggregation stage uses
    (core/comm.py ``edge_membership``).
    """
    pods = 2 if multi_pod else 1
    total_dp = pods * 16
    if n_edges < 1 or n_clients % n_edges:
        raise ValueError(
            f"edge axis {n_edges} must divide the {n_clients} clients "
            f"evenly ({len(jax.devices())} devices visible); nearest "
            f"valid edge counts for {n_clients} clients: "
            f"{_nearest_valid(n_clients, max(n_edges, 1))}")
    if total_dp % n_clients:
        raise ValueError(
            f"client axis {n_clients} must divide the {total_dp}-way "
            f"data parallelism ({len(jax.devices())} devices visible, "
            f"model axis 16); nearest valid cohort sizes: "
            f"{_nearest_valid(total_dp, n_clients)}")
    shape = (n_edges, n_clients // n_edges, total_dp // n_clients, 16)
    return jax.make_mesh(shape, ("edge", "client", "data", "model"),
                         devices=jax.devices()[: _size(shape)])


def make_client_mesh(n_shards: int):
    """1-D ``(client,)`` mesh over the first ``n_shards`` devices."""
    ndev = len(jax.devices())
    if n_shards < 1 or n_shards > ndev:
        raise ValueError(
            f"client_shards={n_shards} needs between 1 and {ndev} "
            f"devices ({ndev} visible)")
    # Auto, not make_mesh's default Explicit: explicit axes put the client
    # split into the array types, which a Pallas kernel downstream of the
    # cohort stage refuses and which make the round's outputs come back
    # typed apart from its committed inputs (a retrace each round)
    return jax.make_mesh((n_shards,), ("client",),
                         (jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:n_shards])


def client_split(n_shards: int):
    """How a per-client operand lies on the ``(client,)`` mesh: its
    leading client axis split into ``n_shards`` device-local blocks.
    ``shard_over_clients`` splits the cohort stage's operands so, and
    the sync feed puts a round's batches there before the round runs."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(make_client_mesh(n_shards), P("client"))


def shard_over_clients(fn, n_shards: int, n_clients: int):
    """Split ``fn``'s leading client axis over a ``(client,)`` mesh.

    ``fn(replicated, *per_client) -> per-client outputs`` — typically a
    vmapped cohort stage: the first argument (a pytree, e.g. global
    params) is replicated, every other argument and every output leaf
    carries a leading client axis that shard_map splits into
    ``n_shards`` device-local blocks, each vmapped on its own device
    group.  Per-client rows are independent, so the result is bitwise
    what the unsharded vmap produces.
    """
    ndev = len(jax.devices())
    if n_clients % n_shards:
        valid = [d for d in range(1, min(n_clients, ndev) + 1)
                 if n_clients % d == 0]
        raise ValueError(
            f"client_shards={n_shards} must divide the cohort of "
            f"{n_clients} clients ({ndev} devices visible); valid "
            f"shard counts here: {valid}")
    split = client_split(n_shards)
    from jax.sharding import PartitionSpec as P

    def wrapped(replicated, *per_client):
        sharded = jax.shard_map(
            fn, mesh=split.mesh,
            in_specs=(P(),) + tuple(split.spec for _ in per_client),
            out_specs=split.spec, check_vma=False)
        return sharded(replicated, *per_client)

    return wrapped


def make_host_mesh(*, model: int = 1):
    """Degenerate 1-device mesh for CPU tests and examples."""
    return jax.make_mesh((1, model), ("data", "model"),
                         devices=jax.devices()[:model])


def _size(shape):
    n = 1
    for s in shape:
        n *= s
    return n
