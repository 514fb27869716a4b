#!/usr/bin/env python3
"""Run the federation round on a TPU once and check what comes out.

    python chip_smoke.py              # one chip: the CNN phase, then the LM phase
    python chip_smoke.py --chips 4    # four chips: the pod-exchange phase only

Everything runs in this one process, which alone touches JAX, and no phase
starts a child process. Phases:

  device  ``jax.devices()[0]`` must be a TPU; anything else exits 1 here,
          before any work, so there is no CPU fallback.
  cnn     The paper CNN: 3 silos x 2 clients, 2 sync rounds, int8 wire,
          MultiKRUM scoring (quantize, gram_q8 and wsum_q8 on the chip).
  lm      qwen3-1.7b at its published widths, cut to 4 layers: 3 silos x 1
          client, 2 sync rounds, int8-delta wire, loss scoring (round 1
          scores through dequantize_batch, round 2 merges with wsum_q8 and
          rebuilds deltas with add_q8_delta).
  pod     (--chips 4) ``make_unifyfl_round_step`` on a ('pod', 'data',
          'model') = (4, 1, 1) mesh, one silo per chip: policy 'all' against
          the mean of four single-chip train steps, and 'top_k' over an int8
          exchange.

Each round phase checks that every loss and score is finite, that the
ledger verifies, that every silo submitted in every round, and that the
fused q8 kernels agree with their ``force="ref"`` oracles on the round's own
int8 payloads. It prints the compile seconds of the phase and the device's
``peak_bytes_in_use`` after every round. Any failed check raises, so the
script exits non-zero; the last line of standard output is the JSON result,
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402  (after the path set-up above)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEED = 0
LM_ARCH = "qwen3-1.7b"
LM_LAYERS = 4           # a dense stack's period is one layer; widths as published
BLOCK = 1 << 17         # payload elements per oracle comparison: one
                        # kernel block, 128 quant tiles
EPS32 = 2.0 ** -23

_COMPILE = {"secs": 0.0, "n": 0}


def _on_duration(event: str, secs: float, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE["secs"] += secs
        _COMPILE["n"] += 1


def log(msg: str = "") -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")
    log(f"  ok  {what}")


def memory() -> tuple:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_in_use")


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# --------------------------------------------------------------------------- #
# Round-phase checks
# --------------------------------------------------------------------------- #

def watch_rounds(orch, tag: str) -> None:
    """Print the device's memory at the end of every round of ``orch``."""
    mark = orch._mark_round

    def marked(rnd, silo_id=None):
        mark(rnd, silo_id)
        peak, used = memory()
        log(f"  {tag} round {rnd}: peak_bytes_in_use={peak} "
            f"bytes_in_use={used}")

    orch._mark_round = marked


def check_rounds(orch, rounds: int) -> None:
    silos = sorted(s.silo_id for s in orch.silos)
    check(orch.ledger.verify(), "ledger verifies")
    for r in range(1, rounds + 1):
        entries = orch.contract.get_round_models(r)
        check(sorted(e.owner for e in entries) == silos,
              f"round {r}: all {len(silos)} silos submitted")
        scores = [float(v) for e in entries for v in e.scores.values()]
        check(scores and all(math.isfinite(v) for v in scores),
              f"round {r}: {len(scores)} scores, all finite")
    losses = [v for s in orch.silos for m in s.metrics
              for v in (m["client_loss"], m["local"]["loss"])]
    check(len(losses) == 2 * rounds * len(silos)
          and all(math.isfinite(v) for v in losses),
          f"{len(losses)} training and eval losses, all finite")


def round_payloads(orch, rnd: int):
    """The round's decoded envelopes, by owner, as silo0's store holds them."""
    silo0 = orch.silos[0]
    entries = sorted(orch.contract.get_round_models(rnd),
                     key=lambda e: e.owner)
    return [silo0.get_decoded(e.cid) for e in entries]


def _blocks(n_padded: int):
    """Payload slices of one kernel block each. Every comparison runs per
    block: an f32 sum over more elements loses too much in the oracles
    (a running sum of squares stops absorbing terms below half its ulp),
    while the fused kernels sum exact per-tile integers."""
    for lo in range(0, n_padded, BLOCK):
        yield lo, min(n_padded, lo + BLOCK)


@functools.partial(jax.jit, static_argnames=("m",))
def _q8_ratios(q, s, w, m: int):
    """err / tol of dequantize_batch, wsum_q8 and multikrum_scores_q8 against
    their oracles on one block of int8 payloads.

    Tolerances, each relative to the largest value it can be rounded
    against (the oracles run at highest matmul precision):
      dequantize_batch  1 ulp: both round the same f32 product q * s.
      wsum_q8           16 ulp of max|oracle|: the kernel folds w into s
                        (one more rounding per term) and sums M terms.
      multikrum         16 * m * sqrt(n) ulp of max|x|^2: d = |x_i|^2 +
                        |x_j|^2 - 2 G_ij cancels when models are close, so
                        its error scales with |x|^2, not with d, and the
                        oracle's f32 sum of n terms drifts by about sqrt(n)
                        ulp (the kernel sums exact per-tile integers)."""
    from repro.kernels import ops, ref

    n = q.shape[1]
    with jax.default_matmul_precision("highest"):
        dq_ref = ops.dequantize_batch(q, s, n, force="ref")
        ws_ref = ops.weighted_sum_q8(q, s, w, force="ref")
        mk_ref = ops.multikrum_scores_q8(q, s, m, force="ref")
        sq_max = jnp.max(ref.gram_q8(q, s, ops.QTILE)[1])
    pairs = (
        (ops.dequantize_batch(q, s, n), dq_ref,
         EPS32 * jnp.max(jnp.abs(dq_ref))),
        (ops.weighted_sum_q8(q, s, w), ws_ref,
         16 * EPS32 * jnp.max(jnp.abs(ws_ref))),
        (ops.multikrum_scores_q8(q, s, m), mk_ref,
         16 * m * math.sqrt(n) * EPS32 * sq_max))
    return jnp.stack([jnp.max(jnp.abs(got - want)) / jnp.maximum(tol, 1e-30)
                      for got, want, tol in pairs])


def _report(what: str, ratios, names) -> None:
    """Fail on the first block whose err/tol exceeds 1; print the worst."""
    r = np.asarray(jnp.stack(ratios))                     # [blocks, checks]
    bad = np.argwhere(r > 1.0)
    if bad.size:
        b, k = bad[0]
        raise RuntimeError(f"check failed: {names[k]} on payload block {b} "
                           f"([{b * BLOCK}:{(b + 1) * BLOCK}]) is {r[b, k]:.3g}"
                           f" x its tolerance")
    worst = ", ".join(f"{nm} {v:.3g}" for nm, v in zip(names, r.max(axis=0)))
    check(True, f"{what} (worst err/tol: {worst})")


def check_q8_kernels(dms, m: int) -> None:
    """Fused dequantize_batch / wsum_q8 / multikrum_scores_q8 against their
    ``force="ref"`` oracles on whole int8 payloads, block by block."""
    from repro.kernels import ops

    q = np.stack([np.asarray(d.q) for d in dms])
    s = np.stack([np.asarray(d.scales) for d in dms])
    w = jnp.asarray(np.linspace(1.0, 2.0, len(dms)) / len(dms), jnp.float32)
    ratios = [_q8_ratios(q[:, lo:hi], s[:, lo // ops.QTILE:hi // ops.QTILE],
                         w, m) for lo, hi in _blocks(q.shape[1])]
    _report(f"fused q8 kernels match their oracles on {len(dms)} payloads "
            f"of {q.shape[1]} elements", ratios,
            ("dequantize_batch", "wsum_q8", "multikrum"))


@functools.partial(jax.jit, static_argnames=("n",))
def _delta_ratio(base, q, s, n: int):
    """err / tol of add_q8_delta against its oracle on one block. Tolerance
    4 ulp of max|oracle|: both compute base + q * s in f32; only the
    rounding of the product may differ (a fused multiply-add)."""
    from repro.kernels import ops

    got = ops.add_q8_delta(base, q, s, n)
    want = ops.add_q8_delta(base, q, s, n, force="ref")
    tol = 4 * EPS32 * jnp.max(jnp.abs(want))
    return jnp.max(jnp.abs(got - want)) / jnp.maximum(tol, 1e-30)[None]


def check_delta_kernel(deltas, bases) -> None:
    """add_q8_delta against its oracle on int8-delta payloads, rebuilt onto
    their decoded bases as ``wire`` does, block by block."""
    from repro.core import wire
    from repro.kernels import ops

    ratios = []
    for d, b in zip(deltas, bases):
        check(d.method == "int8-delta" and d.base_cid,
              f"payload is an int8 delta on {d.base_cid[:12]}")
        q, s = wire.dense_q8_delta(d.tiles, d.q, d.scales, d.n)
        base = b.vec()
        for lo, hi in _blocks(q.shape[0]):
            nb = min(d.n, hi) - lo
            if nb <= 0:
                break
            ratios.append(_delta_ratio(base[lo:lo + nb], q[lo:hi],
                                       s[lo // ops.QTILE:hi // ops.QTILE],
                                       nb))
        del base
    _report(f"add_q8_delta matches its oracle on {len(deltas)} delta "
            f"payloads", ratios, ("add_q8_delta",))


def check_native_kernel(dm) -> None:
    """The fused aggregation compiles to a Mosaic call, not interpret mode."""
    from repro.kernels import ops

    q = jnp.asarray(dm.q)[None]
    s = jnp.asarray(dm.scales)[None]
    w = jnp.ones((1,), jnp.float32)
    text = jax.jit(lambda q, s, w: ops.weighted_sum_q8(q, s, w)).lower(
        q, s, w).compile().as_text()
    if on_tpu():
        check("tpu_custom_call" in text,
              "wsum_q8 compiled as a Mosaic kernel (tpu_custom_call)")
    else:
        log("  --  no TPU: the kernels ran in interpret mode")


def run_phase(tag: str, orch, rounds: int) -> None:
    _COMPILE.update(secs=0.0, n=0)
    watch_rounds(orch, tag)
    t0 = time.perf_counter()
    orch.run(rounds)
    log(f"  {tag}: {rounds} rounds in {time.perf_counter() - t0:.1f} s "
        f"wall, compile {_COMPILE['secs']:.1f} s over {_COMPILE['n']} "
        f"programs")
    check_rounds(orch, rounds)


def release_device_caches() -> None:
    """Drop the process-wide caches that pin one phase's device arrays."""
    from repro.fed import scorebatch

    scorebatch._PREPARED.clear()
    scorebatch._EVAL_FNS.clear()
    gc.collect()


# --------------------------------------------------------------------------- #
# Phases
# --------------------------------------------------------------------------- #

def cnn_phase(*, n_train: int = 3000, n_test: int = 600) -> None:
    from repro.config import FedConfig
    from repro.configs import get_config
    from repro.core.builder import build_image_experiment

    log("phase cnn: paper-cnn, 3 silos x 2 clients, 2 sync rounds, int8, "
        "multikrum")
    fed = FedConfig(n_silos=3, clients_per_silo=2, rounds=2, local_epochs=1,
                    mode="sync", compression="int8", scorer="multikrum")
    orch = build_image_experiment(get_config("paper-cnn"), fed,
                                  n_train=n_train, n_test=n_test, seed=SEED)
    run_phase("cnn", orch, fed.rounds)
    dms = round_payloads(orch, fed.rounds)
    check(all(d.is_q8 for d in dms), "round 2 payloads are whole int8")
    check_q8_kernels(dms, fed.multikrum_m)
    check_native_kernel(dms[0])
    del orch, dms
    release_device_caches()


def lm_phase(cfg, *, steps_per_epoch: int = 8) -> None:
    from repro.config import FedConfig
    from repro.core.builder import build_lm_experiment
    from repro.kernels import ops

    # measured compute enters the simulated clock, and at these widths one
    # scorer's batch (compiles included) outlasts the default 5 s scoring
    # deadline, which would drop its scores as late: no deadline here
    fed = FedConfig(n_silos=3, clients_per_silo=1, rounds=2, local_epochs=1,
                    mode="sync", compression="int8-delta", scorer="loss",
                    scorer_deadline_s=0.0)
    orch = build_lm_experiment(cfg, fed, steps_per_epoch=steps_per_epoch,
                               seed=SEED)
    n = ops.spec_length(orch.silos[0].flat_spec())
    log(f"phase lm: {cfg.arch_id}, {cfg.n_layers} layers, d_model="
        f"{cfg.d_model}, vocab={cfg.vocab_size}, {n} parameters; 3 silos x "
        f"1 client, 2 sync rounds, int8-delta, loss scoring")
    run_phase("lm", orch, fed.rounds)
    whole = round_payloads(orch, 1)
    check(all(d.is_q8 for d in whole), "round 1 payloads are whole int8")
    check_q8_kernels(whole, fed.multikrum_m)
    deltas = round_payloads(orch, 2)
    by_cid = {e.cid: e for e in orch.contract.get_round_models(1)}
    bases = [orch.silos[0].get_decoded(d.base_cid) for d in deltas]
    check(all(d.base_cid in by_cid for d in deltas),
          "round 2 deltas reference round 1 models")
    check_delta_kernel(deltas, bases)
    check_native_kernel(whole[0])
    del orch, whole, deltas, bases
    release_device_caches()


def pod_phase(cfg, *, batch: int = 8, seq: int = 128,
              lr: float = 0.1) -> None:
    """make_unifyfl_round_step on a (n, 1, 1) mesh of all devices, one silo
    per device, against single-device references."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import pshard
    from repro.core.exchange import (ExchangeConfig, make_train_step,
                                     make_unifyfl_round_step)
    from repro.launch.mesh import make_production_mesh
    from repro.models import build_model

    n_pods = len(jax.devices())
    log(f"phase pod: {cfg.arch_id}, {cfg.n_layers} layers, one silo per "
        f"device on a ('pod', 'data', 'model') = ({n_pods}, 1, 1) mesh")
    _COMPILE.update(secs=0.0, n=0)
    mesh = make_production_mesh(multi_pod=True, shape=(n_pods, 1, 1))
    devices = list(mesh.devices.flat)
    pod = NamedSharding(mesh, P("pod"))
    model = build_model(cfg)
    keys = jax.random.split(jax.random.PRNGKey(SEED), n_pods)
    stacked = jax.jit(jax.vmap(model.init), out_shardings=pod)(keys)
    toks = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                              (n_pods, batch, seq), 0, cfg.vocab_size)
    data = jax.device_put({"tokens": toks,
                           "targets": jnp.roll(toks, -1, axis=2)}, pod)

    def on_own_devices(tree, what):
        ok = True
        for leaf in jax.tree.leaves(tree):
            shards = sorted(leaf.addressable_shards,
                            key=lambda sh: sh.index[0].start or 0)
            ok &= [sh.device for sh in shards] == devices
            ok &= all(sh.data.shape[0] == 1 for sh in shards)
        check(ok, f"{what}: silo i's block sits on device i only")

    def silo(tree, i):
        """Silo i's block, on device i."""
        return jax.tree.map(
            lambda a: sorted(a.addressable_shards,
                             key=lambda sh: sh.index[0].start or 0)[i].data[0],
            tree)

    on_own_devices(stacked, "initial params")
    with pshard.use_mesh(mesh):
        step_all = jax.jit(make_unifyfl_round_step(
            model, mesh, ExchangeConfig(policy="all"), lr=lr))
        out_all, loss_all = step_all(stacked, data)
        jax.block_until_ready(out_all)
    on_own_devices(out_all, "policy 'all' output")
    losses = np.asarray(loss_all, np.float32)
    check(np.all(np.isfinite(losses)), f"per-silo losses {losses.tolist()}")

    # the reference: each silo's single-device step, then the plain mean.
    # Tolerance 5e-2 (abs and rel): params are bf16, and the round step and
    # the references round their bf16 updates in differently fused programs.
    ts = jax.jit(make_train_step(model, lr=lr))
    refs = [ts(silo(stacked, i), silo(data, i))[0] for i in range(n_pods)]
    worst, spread = 0.0, 0.0
    for j, leaf in enumerate(jax.tree.leaves(out_all)):
        want = np.mean([np.asarray(jax.tree.leaves(r)[j], np.float32)
                        for r in refs], axis=0)
        got = [np.asarray(sh.data[0], np.float32) for sh in sorted(
            leaf.addressable_shards, key=lambda sh: sh.index[0].start or 0)]
        for g in got:
            np.testing.assert_allclose(g, want, rtol=5e-2, atol=5e-2)
            worst = max(worst, float(np.max(np.abs(g - want))))
            spread = max(spread, float(np.max(np.abs(g - got[0]))))
    check(spread <= 1e-5, f"all silos hold the same merged model (max "
                          f"spread {spread})")
    check(True, f"policy 'all' equals the mean of {n_pods} single-device "
                f"steps (max abs diff {worst})")
    del refs, out_all

    with pshard.use_mesh(mesh):
        step_tk = jax.jit(make_unifyfl_round_step(
            model, mesh, ExchangeConfig(policy="top_k", k=1,
                                        compression="int8"), lr=lr))
        out_tk, loss_tk = step_tk(stacked, data)
        jax.block_until_ready(out_tk)
    on_own_devices(out_tk, "policy 'top_k' int8 output")
    check(all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32))))
              for l in jax.tree.leaves(out_tk)),
          "policy 'top_k' over the int8 exchange: every parameter finite")
    peak, used = memory()
    log(f"  pod: compile {_COMPILE['secs']:.1f} s over {_COMPILE['n']} "
        f"programs; device 0 peak_bytes_in_use={peak} bytes_in_use={used}")


# --------------------------------------------------------------------------- #

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: the CNN and LM round phases on one chip; "
                        "4: the pod-exchange phase on four chips, only")
    args = p.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: jax.devices()[0] is {dev.platform} "
              f"({dev.device_kind}); this script runs on the chip only",
              file=sys.stderr)
        return 1
    if args.chips == 4 and len(devices) < 4:
        print(f"--chips 4 needs four devices, found {len(devices)}",
              file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.config import replace
    from repro.launch.cache import enable_compile_cache

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; jax {jax.__version__}; compile cache "
        f"{enable_compile_cache()}")
    lm_cfg = replace(get_config(LM_ARCH), n_layers=LM_LAYERS)
    if args.chips == 4:
        pod_phase(lm_cfg)
    else:
        cnn_phase()
        lm_phase(lm_cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
