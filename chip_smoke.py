"""Smoke run of the main paths on a TPU, each checked against its reference.

    python3 chip_smoke.py                # one chip: train, codec, serve
    python3 chip_smoke.py --four-chips   # the cohort split over 4 chips

Phases (one process drives every chip; weights and data come from --seed):

* ``train`` — the paper's VGG16 at full width through
  ``Federation.from_config(...).fit``: 8 clients, 25 % of the 14 freeze
  units trained per round, hub topology, dense path with
  ``fused_agg="auto"`` (the fused Pallas FedAvg kernel on TPU), 3 rounds.
  The compiled round step must hold a ``tpu_custom_call`` and the
  ``fused_agg="off"`` step (the jnp reduce) none; the kernel must match
  the jnp reduce on the same deltas within ``AGG_ATOL``; losses must be
  finite; a pair of runs at ``COMPARE_PRECISION``, fused and
  ``fused_agg="off"``, must end within ``TRAIN_RTOL`` and ``LOGITS_RTOL``
  of each other (the default-precision pair's distance is printed).
* ``codec`` — the same model on the packed path with ``codec="qint8"``,
  2 rounds: the round step must hold the quantize-pack kernel, the kernel
  must equal ``kernels/codec/ref.py`` bitwise on the chip (int8 and int4),
  and ``comm_summary()`` must bill the encoded wire bytes.
* ``serve`` — qwen3-1.7b at its published widths in a ``DecodeEngine``
  (4 slots, page size 16) serving 8 greedy requests; the decode step must
  hold the paged flash-decode kernel, and every request's per-step logits
  must match ``static_generate`` (dense cache, jnp attention) within
  ``SERVE_RTOL`` of the largest reference logit.
* ``--four-chips`` runs only ``shards``: the train phase with
  ``client_shards=4``, timed at default precision; the cohort split's
  per-client gradients must sit one shard per chip and match the stage
  vmapped on one device within ``STAGE_RTOL``; a pair of federated runs
  at ``COMPARE_PRECISION``, split and on one device, is printed.

Exits non-zero, printing no result, where JAX finds no TPU.  Timings are
one smoke run, not a benchmark.  The last line is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.core import FLConfig, Federation, ModelSpec  # noqa: E402
from repro.core.aggregation import masked_fedavg  # noqa: E402
from repro.core.codecs import codec_unit_bytes, get_codec  # noqa: E402
from repro.data import FederatedLoader, cifar_like, iid_partition  # noqa: E402
from repro.kernels.codec import quantize_pack, quantize_pack_ref  # noqa: E402
from repro.kernels.masked_agg.ops import masked_fedavg_fused  # noqa: E402
from repro.launch.env import enable_compile_cache  # noqa: E402
from repro.launch.mesh import shard_over_clients  # noqa: E402
from repro.models import get_model, paper_models as pm  # noqa: E402
from repro.serve.engine import (DecodeEngine, ServeConfig,  # noqa: E402
                                static_generate)

KERNEL = "tpu_custom_call"
# Two federated runs that differ in how the FedAvg sum is computed (fused
# kernel vs jnp reduce, or 4 chips vs 1) differ by f32 rounding, which
# Adam does not keep small: its first step moves every element by about
# lr * sign(g), so where the true gradient is zero (the conv biases that
# BatchNorm cancels) rounding noise picks the sign, and the runs part by
# up to 2 * lr per step in those elements, and in weights whose gradient
# is near zero.  So the params are held to
# |run - reference|_2 <= TRAIN_RTOL * |reference - init|_2, and the
# function they compute (logits on held-out images) to LOGITS_RTOL of the
# largest reference logit.  A scrambled tile, unit or weight moves both
# by order 1.
TRAIN_RTOL = 0.1
LOGITS_RTOL = 5e-2
# At DEFAULT precision a TPU convolution rounds its inputs to bf16, so a
# last-bit difference in one activation can flip the rounding of the next
# layer's input: two runs whose FedAvg sums differ in f32 rounding part at
# bf16 size (on a v5e, fused vs jnp reduce gave 0.634 of the update after
# 3 rounds, though the two reduces agree within 3e-8 on the same deltas).
# So the runs held to TRAIN_RTOL are made at "highest" precision, f32 as
# on the CPU; the timed run is the default one, and the default pair's
# distance is printed, not held.
COMPARE_PRECISION = "highest"
# The cohort split runs each client's convolutions in a batch of 2
# clients, not 8, so its local training differs from one device's in
# rounding from the first step, and the federated runs part past
# TRAIN_RTOL within 3 rounds even in f32 (the CPU at width 0.25: 0.14 of
# the update after round 2 with Adam, 0.16 after round 3 with SGD).  So
# --four-chips holds the stage itself, per-client gradients on the same
# inputs, to f32 rounding through 16 layers; a client's gradient taken
# on another client's batch is off by order 1.  The run pair is printed.
STAGE_RTOL = 1e-3
# the fused reduce alone vs the jnp reduce on the same deltas: f32 sums
# of 8 clients in another order, a few ulp of params of order 1
AGG_ATOL = 1e-6
# engine (paged Pallas attention) vs static loop (dense jnp attention),
# both at "highest" matmul precision, relative to the largest |logit|
SERVE_RTOL = 1e-3


@dataclasses.dataclass(frozen=True)
class Size:
    """What each phase runs.  The script runs ``FULL``;
    tests/test_chip_smoke.py runs the phases on the CPU at a tiny size."""
    vgg_width: float = 1.0
    clients: int = 8
    per_client: int = 64            # examples per client
    batch: int = 32
    steps: int = 2                  # local steps per round
    fused_agg: str = "auto"
    expect_kernel: bool = True      # the compiled steps hold Pallas calls
    codec_rows: tuple = ((8, 2359296), (9, 37))   # conv 512x512 leaf x C
    arch: str = "qwen3-1.7b"
    reduced: bool = False
    prompt_lens: tuple = (32, 128)
    gen_range: tuple = (16, 32)
    n_requests: int = 8
    slots: int = 4


FULL = Size()


def log(msg):
    print(f"[smoke] {msg}", flush=True)


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def host(tree):
    return [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(tree)]


def compare_runs(tag, params, ref_params, init, seed):
    """Log how far ``params`` is from ``ref_params``; False past the
    tolerances (see TRAIN_RTOL)."""
    a, b = host(params), host(ref_params)
    bitwise = all(np.array_equal(x, y) for x, y in zip(a, b))
    max_abs = max(float(np.max(np.abs(x - y))) for x, y in zip(a, b))
    l2 = lambda xs, ys: float(np.sqrt(sum(np.sum((x - y) ** 2)
                                          for x, y in zip(xs, ys))))
    rel = l2(a, b) / l2(b, init)
    x_eval, _ = cifar_like(64, key=seed + 7)
    apply = jax.jit(pm.vgg16_apply)
    la = np.asarray(apply(params, x_eval), np.float64)
    lb = np.asarray(apply(ref_params, x_eval), np.float64)
    lrel = float(np.max(np.abs(la - lb)) / np.max(np.abs(lb)))
    log(f"{tag}: bitwise {bitwise}; max |params diff| {max_abs!r}; "
        f"|diff|_2 / |reference - init|_2 {rel!r} (tolerance "
        f"{TRAIN_RTOL}); logits max |diff| / max |logit| {lrel!r} "
        f"(tolerance {LOGITS_RTOL})")
    return rel <= TRAIN_RTOL and lrel <= LOGITS_RTOL


def compare_pair(tag, seed, size, fl_kw, ref_fl_kw, rounds=3):
    """Run two federations that differ in ``fl_kw`` / ``ref_fl_kw`` side
    by side at COMPARE_PRECISION, printing their distance after every
    round; False if the last is past the tolerances."""
    with jax.default_matmul_precision(COMPARE_PRECISION):
        run = vgg_federation(seed, size, **fl_kw)
        ref = vgg_federation(seed, size, **ref_fl_kw)
        init = host(ref.params)
        for r in range(1, rounds + 1):
            run.fit(1)
            ref.fit(1)
            close = compare_runs(f"{tag} at {COMPARE_PRECISION} precision,"
                                 f" round {r}", run.params, ref.params,
                                 init, seed)
    return close


# ---------------------------------------------------------------------------
# federated round


def vgg_federation(seed, size, **fl_kw):
    def loss_fn(p, batch):
        return pm.xent_loss(pm.vgg16_apply(p, batch["x"]), batch["y"]), {}

    spec = ModelSpec(
        name="vgg16",
        init_params=functools.partial(pm.init_vgg16,
                                      width_mult=size.vgg_width),
        loss_fn=loss_fn, unit_order=pm.vgg16_units)
    n = size.clients * size.per_client
    x, y = cifar_like(n, key=seed)
    shards = iid_partition(n, size.clients, key=seed + 1)
    loader = FederatedLoader([{"x": x[s], "y": y[s]} for s in shards],
                             batch_size=size.batch,
                             steps_per_round=size.steps, key=seed)
    fl = FLConfig(n_clients=size.clients, train_fraction=0.25,
                  topology="hub", lr=3e-3, **fl_kw)
    return Federation.from_config(spec, fl, data=loader, seed=seed)


def compile_round(fed):
    """AOT-compile the round step at the signature ``fit`` calls it with
    (``fit`` then reuses the program); returns (compiled, seconds)."""
    batches = fed.server.place_batches(fed.loader.round_batches(0))
    weights = jnp.asarray(fed.loader.weights())
    t0 = time.perf_counter()
    compiled = fed.server.round_step.lower(
        fed.state, batches, weights, jax.random.PRNGKey(0)).compile()
    return compiled, time.perf_counter() - t0


def fit_timed(fed, rounds):
    secs = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        fed.fit(1)
        jax.block_until_ready(fed.state)
        secs.append(time.perf_counter() - t0)
    losses = [r.loss for r in fed.history]
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite round loss: {losses}")
    return secs, losses


def agg_vs_reference(fed, seed):
    """The fused FedAvg reduce against the jnp one, on random deltas and
    selections of the federation's model and cohort."""
    c, assign = fed.fl.n_clients, fed.assign
    key = jax.random.PRNGKey(seed + 3)
    deltas = jax.tree_util.tree_map(
        lambda p: 0.05 * jax.random.normal(key, (c,) + p.shape), fed.params)
    sel = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.25,
                               (c, assign.n_units)).astype(jnp.float32)
    w = jnp.asarray(fed.loader.weights())
    got = jax.jit(lambda *a: masked_fedavg_fused(*a, assign))(
        fed.params, deltas, sel, w)
    ref = jax.jit(lambda *a: masked_fedavg(*a, assign))(
        fed.params, deltas, sel, w)
    return max(float(np.max(np.abs(a - b)))
               for a, b in zip(host(got), host(ref)))


def phase_train(seed, size):
    fed = vgg_federation(seed, size, fused_agg=size.fused_agg)
    compiled, t_compile = compile_round(fed)
    has_kernel = KERNEL in compiled.as_text()
    log(f"train: round step compile {t_compile:.3f} s, {KERNEL} "
        f"(masked_agg) present: {has_kernel}")
    agg_diff = agg_vs_reference(fed, seed)
    log(f"train: masked_agg kernel vs jnp masked_fedavg on VGG16 deltas of "
        f"{size.clients} clients: max |diff| {agg_diff!r} (tolerance "
        f"{AGG_ATOL})")
    secs, losses = fit_timed(fed, 3)
    log(f"train: round seconds {secs}, losses {losses}")

    ref = vgg_federation(seed, size, fused_agg="off")
    init = host(ref.params)
    ref_compiled, t_ref = compile_round(ref)
    ref_kernel = KERNEL in ref_compiled.as_text()
    log(f"train: reference (fused_agg=off) compile {t_ref:.3f} s, "
        f"{KERNEL} present: {ref_kernel}")
    fit_timed(ref, 3)
    compare_runs("train: vs fused_agg=off at default precision (printed, "
                 "not held)", fed.params, ref.params, init, seed)
    close = compare_pair("train: vs fused_agg=off", seed, size,
                         dict(fused_agg=size.fused_agg),
                         dict(fused_agg="off"))
    log(f"train: peak_bytes_in_use {peak_bytes()}")
    if size.expect_kernel and not has_kernel:
        raise AssertionError("the round step holds no Pallas kernel")
    if ref_kernel:
        raise AssertionError("the fused_agg=off round step holds a kernel")
    if not agg_diff <= AGG_ATOL:
        raise AssertionError(f"the fused reduce is off by {agg_diff}")
    if not close:
        raise AssertionError("params differ from the reference run")


def phase_codec(seed, size):
    fed = vgg_federation(seed, size, fused_agg=size.fused_agg,
                            packed=True, codec="qint8")
    compiled, t_compile = compile_round(fed)
    has_kernel = KERNEL in compiled.as_text()
    log(f"codec: round step compile {t_compile:.3f} s, {KERNEL} "
        f"(quantize_pack) present: {has_kernel}")
    secs, losses = fit_timed(fed, 2)
    log(f"codec: round seconds {secs}, losses {losses}")

    sel = np.stack(fed.server.sel_history)                   # (R, C, U)
    wire = codec_unit_bytes(get_codec("qint8"), fed.assign, fed.params,
                            fed.fl)
    fp32 = codec_unit_bytes(get_codec("none"), fed.assign, fed.params,
                            fed.fl)
    expect = float(np.einsum("rcu,u->", sel, wire))
    fp32_total = float(np.einsum("rcu,u->", sel, fp32))
    billed = fed.comm_summary()["total_uplink_bytes"]
    log(f"codec: billed uplink {billed!r} B, encoded {expect!r} B, "
        f"fp32 {fp32_total!r} B")

    bad = []
    key = jax.random.PRNGKey(seed)
    for shape in size.codec_rows:
        x = jax.random.normal(key, shape) * jnp.linspace(
            0.1, 10.0, shape[0])[:, None]
        u = jax.random.uniform(jax.random.fold_in(key, 1), shape)
        for bits in (8, 4):
            pk, sk = jax.jit(functools.partial(quantize_pack,
                                               bits=bits))(x, u)
            pr, sr = jax.jit(functools.partial(quantize_pack_ref,
                                               bits=bits))(x, u)
            codes = int(np.sum(np.asarray(pk) != np.asarray(pr)))
            scales = int(np.sum(np.asarray(sk) != np.asarray(sr)))
            log(f"codec: quantize_pack q{bits} {shape} vs reference: "
                f"{codes} packed bytes and {scales} scales differ "
                f"(tolerance: bitwise)")
            if codes or scales:
                bad.append((bits, shape))
    log(f"codec: peak_bytes_in_use {peak_bytes()}")
    if size.expect_kernel and not has_kernel:
        raise AssertionError("the packed qint8 round step holds no kernel")
    if bad:
        raise AssertionError(f"quantize_pack differs from ref.py: {bad}")
    if billed != expect:
        raise AssertionError(f"billed {billed} B, encoded {expect} B")
    if not billed * 3.5 <= fp32_total:
        raise AssertionError("qint8 bills less than a 3.5x cut")


def phase_shards(seed, size):
    fed = vgg_federation(seed, size, fused_agg=size.fused_agg,
                            client_shards=4)
    compiled, t_compile = compile_round(fed)
    log(f"shards: client_shards=4 round step compile {t_compile:.3f} s, "
        f"{KERNEL} present: {KERNEL in compiled.as_text()}")
    secs, losses = fit_timed(fed, 3)
    log(f"shards: round seconds {secs}, losses {losses}")
    # the split the round step runs its cohort stage through, here over
    # per-client gradients on this round's batches: its outputs must sit
    # one shard per chip and equal the stage vmapped on one device
    grads = jax.vmap(jax.grad(lambda p, x, y: pm.xent_loss(
        pm.vgg16_apply(p, x), y)), in_axes=(None, 0, 0))
    batch = fed.loader.round_batches(0)
    x, y = jnp.asarray(batch["x"][:, 0]), jnp.asarray(batch["y"][:, 0])
    with jax.default_matmul_precision(COMPARE_PRECISION):
        split = jax.jit(shard_over_clients(grads, 4, size.clients))(
            fed.params, x, y)
        whole = jax.jit(grads)(fed.params, x, y)
    leaf = jax.tree_util.tree_leaves(split)[0]
    homes = sorted((s.device.id, tuple(s.data.shape))
                   for s in leaf.addressable_shards)
    log(f"shards: cohort stage output {leaf.sharding} -> "
        f"(device, shard shape) {homes}")
    a, b = host(split), host(whole)
    stage_rel = float(np.sqrt(sum(np.sum((u - v) ** 2)
                                  for u, v in zip(a, b)))
                      / np.sqrt(sum(np.sum(v ** 2) for v in b)))
    log(f"shards: per-client gradients, 4 chips vs one device at "
        f"{COMPARE_PRECISION} precision: |diff|_2 / |grad|_2 "
        f"{stage_rel!r} (tolerance {STAGE_RTOL})")

    compare_pair("shards: vs one device (printed, not held)", seed, size,
                 dict(fused_agg=size.fused_agg, client_shards=4),
                 dict(fused_agg=size.fused_agg))
    log(f"shards: peak_bytes_in_use {peak_bytes()}")
    per = (size.clients // 4,) + leaf.shape[1:]
    if len({d for d, _ in homes}) != 4 or \
            any(shape != per for _, shape in homes):
        raise AssertionError(f"per-client outputs are not one shard per "
                             f"chip: {homes}")
    if not stage_rel <= STAGE_RTOL:
        raise AssertionError("the split cohort stage differs from one "
                             "device")


# ---------------------------------------------------------------------------
# paged decode


def phase_serve(seed, size):
    cfg = get_config(size.arch)
    if size.reduced:
        cfg = cfg.reduced()
    model = get_model(cfg)
    rng = np.random.default_rng(seed)
    lens = [size.prompt_lens[i % len(size.prompt_lens)]
            for i in range(size.n_requests)]
    gens = [int(g) for g in rng.integers(size.gen_range[0],
                                         size.gen_range[1] + 1,
                                         size.n_requests)]
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]
    max_len = max(lens) + max(gens)

    with jax.default_matmul_precision("highest"):
        params = jax.jit(model.init_params)(jax.random.PRNGKey(seed))
        eng = DecodeEngine(cfg, params, ServeConfig(
            n_slots=size.slots, max_len=max_len, page_size=16,
            record_logits=True))
        t0 = time.perf_counter()
        compiled = eng.compiled_decode()
        t_compile = time.perf_counter() - t0
        has_kernel = KERNEL in compiled.as_text()
        log(f"serve: {cfg.name} d_model {cfg.d_model} layers "
            f"{cfg.n_layers} vocab {cfg.vocab}; decode step compile "
            f"{t_compile:.3f} s, {KERNEL} (flash_decode_paged) present: "
            f"{has_kernel}")
        rids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        t0 = time.perf_counter()
        res = eng.run()
        t_first = time.perf_counter() - t0
        # the same requests again: every program is compiled by now
        for p, g in zip(prompts, gens):
            eng.submit(p, g)
        t0 = time.perf_counter()
        res2 = eng.run()
        t_warm = time.perf_counter() - t0
        n_tok = sum(gens)
        log(f"serve: {len(prompts)} requests, prompts {lens}, new tokens "
            f"{gens}; first run {t_first:.3f} s (prefill compiles "
            f"included), warm run {t_warm:.3f} s = "
            f"{t_warm / n_tok!r} s per generated token")

        refs = {}
        for n in sorted(set(lens)):
            group = [i for i in range(len(lens)) if lens[i] == n]
            out, rows = static_generate(
                cfg, params, jnp.asarray(np.stack([prompts[i]
                                                   for i in group])),
                max(gens[i] for i in group), max_len=eng.layout.max_len,
                rids=np.asarray([rids[i] for i in group]),
                collect_logits=True)
            for j, i in enumerate(group):
                refs[i] = (out[j], [r[j] for r in rows])

    worst, flips, bad = 0.0, [], []
    for i, rid in enumerate(rids):
        if not np.array_equal(res[rid], res2[rid + len(rids)]):
            bad.append(f"request {rid}: warm rerun changed its tokens")
        out, rows = refs[i]
        for t in range(gens[i]):
            e = np.asarray(eng.logits_rows[rid][t], np.float64)
            s = np.asarray(rows[t], np.float64)
            scale = float(np.max(np.abs(s)))
            tol = SERVE_RTOL * scale
            d = float(np.max(np.abs(e - s)))
            worst = max(worst, d / scale)
            if d > tol:
                bad.append(f"request {rid} token {t}: |logits diff| {d} "
                           f"> {tol}")
                break
            te, ts = int(res[rid][t]), int(out[t])
            if te != ts:
                # greedy picked another token: allowed only on a tie
                # within the tolerance; the contexts part after it
                gap = abs(float(s[te] - s[ts]))
                flips.append((rid, t, gap))
                if gap > 2 * tol:
                    bad.append(f"request {rid} token {t}: engine chose "
                               f"{te}, reference {ts}, gap {gap}")
                break
    log(f"serve: max |engine - static logits| / max|static logit| = "
        f"{worst!r} (tolerance {SERVE_RTOL}); greedy ties that parted "
        f"the streams (request, token, gap): {flips}")
    log(f"serve: decode programs {eng.decode_cache_size}; "
        f"peak_bytes_in_use {peak_bytes()}")
    if size.expect_kernel and not has_kernel:
        raise AssertionError("the decode step holds no Pallas kernel")
    if eng.decode_cache_size != 1:
        raise AssertionError("the decode step compiled more than once")
    if bad:
        raise AssertionError("; ".join(bad))


# ---------------------------------------------------------------------------


def run(phases, seed, size):
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(seed, size)
        except Exception:
            traceback.print_exc()
            failed.append(name)
        log(f"{name}: {'FAILED' if name in failed else 'passed'} in "
            f"{time.perf_counter() - t0:.3f} s")
    return failed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the client_shards=4 phase (4 chips)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev.platform!r}); nothing was run")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        sys.exit(f"chip_smoke: needs {want} chips, JAX found "
                 f"{len(devices)}")
    log(f"single smoke run, not a benchmark: {dev.device_kind} x "
        f"{len(devices)}, jax {jax.__version__}, compile cache "
        f"{enable_compile_cache()}")

    if args.four_chips:
        phases = [("shards", phase_shards)]
    else:
        phases = [("train", phase_train), ("codec", phase_codec),
                  ("serve", phase_serve)]
    failed = run(phases, args.seed, FULL)
    if failed:
        sys.exit(f"chip_smoke: failed phases {failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
