"""Batched scoring benchmark: what does a round's validation *cost*?

Every round, each scorer evaluates every pulled peer model on its private
test set (paper §2.6) — K models × S scorers of forward passes, the
scalability bottleneck of trustless cross-silo schemes. This bench times
one (scorer, round) score call both ways on the paper CNN:

  * **sequential** — the pre-engine shape: per model, decode the wire
    payload, dequantize, unflatten, then one jitted forward per batch with
    a ``float()`` device→host sync per batch (2 syncs: loss + accuracy).
  * **batched** — ``repro.fed.scorebatch``: the round's mixed q8/raw
    envelopes stack through the batched-dequant ingest and score in ONE
    ``lax.scan`` × ``vmap`` jit, one device→host transfer for the whole
    [K] score vector.

Both paths start from the same serialized store payloads (half int8, half
raw) and use the same eval batch width, so the delta is purely the engine's
restructuring. Results land in ``BENCH_scoring.json``; the schema and the
acceptance invariants (speedup >= 3x at K >= 4, exactly one host sync per
batched call, score parity <= 1e-5) are asserted by
``tests/test_scorebench_schema.py``.
"""
from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.common import CNN, emit, timed
from repro.core import wire
from repro.core.store import deserialize_pytree, serialize_pytree
from repro.fed import scorebatch
from repro.kernels import ops
from repro.models import build_model


class _ScorerSilo:
    """Duck-typed cluster for the engine: a model + a private test set."""

    def __init__(self, model, test_data):
        self.model = model
        self.test_data = test_data


def _round_payloads(model, k: int, seed: int = 0):
    """K serialized peer envelopes (mixed wire: even = int8, odd = raw)."""
    base, spec = ops.flatten_pytree(model.init(jax.random.PRNGKey(seed)))
    rng = np.random.default_rng(seed)
    flats, methods = [], []
    for i in range(k):
        v = jnp.asarray(np.asarray(base)
                        + rng.normal(0, 0.05, base.shape).astype(np.float32))
        method = "int8" if i % 2 == 0 else "raw"
        flats.append(deserialize_pytree(serialize_pytree(
            wire.encode_vec(v, method).to_store())))
        methods.append(method)
    return flats, spec, methods


def _time_min_interleaved(fns, iters: int):
    """Best-of-``iters`` wall time for each fn, measured interleaved (A, B,
    A, B, ...) so load/thermal drift during the run hits every candidate
    equally — the reported *ratio* is what must stay stable."""
    for fn in fns:
        fn()  # warmup (compile)
    best = [float("inf")] * len(fns)
    for _ in range(iters):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            fn()
            best[i] = min(best[i], time.perf_counter() - t0)
    return best


def main(quick: bool = True, out_path: str = "BENCH_scoring.json",
         trace_path: str = "") -> Dict:
    k = 12 if quick else 16
    n_test = 192 if quick else 1024
    bs = 32 if quick else 128
    iters = 7 if quick else 9

    model = build_model(CNN)
    rng = np.random.default_rng(1)
    td = {"x": rng.normal(0, 1, (n_test, 32, 32, 3)).astype(np.float32),
          "y": rng.integers(0, 10, n_test).astype(np.int32)}
    silo = _ScorerSilo(model, td)
    silo._batched_scorer = scorebatch.BatchedScorer(silo, batch_size=bs)
    flats, spec, methods = _round_payloads(model, k)

    with timed("scorebench"):
        # -- sequential: one jitted forward per (model, batch), 2 host
        # syncs per batch — the pre-engine Cluster.evaluate loop shape ----- #
        ev = jax.jit(lambda p, b: model.loss(p, b)[1])
        seq_syncs = [0]

        def sequential():
            seq_syncs[0] = 0
            out = []
            for flat in flats:
                dm = wire.decode_store(flat)
                params = ops.unflatten_pytree(dm.vec(), spec)
                acc = 0.0
                for i in range(0, n_test, bs):
                    batch = {"image": jnp.asarray(td["x"][i:i + bs]),
                             "label": jnp.asarray(td["y"][i:i + bs])}
                    m = ev(params, batch)
                    c = len(td["x"][i:i + bs])
                    float(m["loss"])                       # host sync
                    acc += float(m.get("accuracy", 0.0)) * c  # host sync
                    seq_syncs[0] += 2
                out.append(acc / n_test)
            return out

        # -- batched: q8-direct ingest + one scan x vmap pass -------------- #
        def batched():
            decoded = [wire.decode_store(f) for f in flats]
            return scorebatch.score_round_batch(silo, decoded, spec,
                                                method="accuracy")

        seq_scores = sequential()
        engine = scorebatch.get_scorer(silo)
        syncs_before = engine.host_syncs
        bat_scores = batched()
        batched_syncs = engine.host_syncs - syncs_before

        seq_s, bat_s = _time_min_interleaved((sequential, batched), iters)
        speedup = seq_s / max(bat_s, 1e-12)
        parity = max(abs(a - b) for a, b in zip(seq_scores, bat_scores))

        emit("score_sequential_s", f"{seq_s:.4f}",
             f"K={k} x {n_test} examples, {seq_syncs[0]} host syncs/round")
        emit("score_batched_s", f"{bat_s:.4f}",
             f"{batched_syncs} host sync/round")
        emit("score_speedup", f"{speedup:.2f}", "sequential / batched")
        emit("score_parity_max_abs_diff", f"{parity:.2e}", "accuracy scores")

    out = {
        "quick": quick,
        "config": {"model": CNN.arch_id, "k": k, "n_test": n_test,
                   "batch_size": bs,
                   "wire_methods": {m: methods.count(m) for m in set(methods)}},
        "sequential_wall_s": seq_s,
        "batched_wall_s": bat_s,
        "speedup": speedup,
        "host_syncs": {"sequential_per_round": seq_syncs[0],
                       "batched_per_round": batched_syncs},
        "parity_max_abs_diff": parity,
    }
    common.write_artifact(out, out_path)
    if trace_path:
        # host-clock benchmark: export the timed sections as the trace
        common.write_host_trace(trace_path)
    ok = (speedup >= 3.0 and batched_syncs == 1 and parity <= 1e-5)
    common.emit_acceptance(
        "score", ok,
        "batched >= 3x sequential at K >= 4, one device->host transfer "
        "per (scorer, round), parity <= 1e-5")
    return out


if __name__ == "__main__":
    common.bench_cli(main, doc=__doc__, default_out="BENCH_scoring.json")
