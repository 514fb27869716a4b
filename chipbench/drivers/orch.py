"""Driver of the program's round engine: ``SyncOrchestrator.run``.

Set-up builds the experiment through the program's own builder from the
seed, runs the warm-up round (round 1, which also takes the training
capture below), and warms the merge programs, which first run in round 2,
at the cell's shapes through the program's own aggregator. The window is
then the following rounds of the same ``orch.run`` call: a hook on
``_mark_round`` (the engine's round boundary) records each round's end and
raises ``WindowClosed`` after the round that ends past ``--seconds``, so
nothing after the last measured round runs.

What ``correct`` compares (all on the host, against plain references that
share no code with the program):

  loss_gap         the first three local SGD steps of one client drawn from
  grad_gap         the seed (round 1, through the window's own compiled step
  update_gap       and feed), against the reference model's three steps from
                   the seed's init on the same batches: each step's loss,
                   the first gradient (from the params after one step) and
                   the change after three steps, by worst leaf.
  encode_mismatch  every int8 envelope published after the warm-up round
                   against the reference quantizer run on the params it
                   encoded: the share of elements whose decoded values
                   disagree.
  peer_mismatch    every peer payload that a merge or the MultiKRUM scoring
                   consumed after the warm-up round against the envelopes
                   published for it (a merge in round r takes the other
                   silos' envelopes of round r - 1, the scoring those of
                   round r), bit for bit: consumed payloads that match none,
                   repeat one, or are missing where the policy takes all.
  merge_gap        every cross-silo merge against the mean of the silo's own
                   params and the dequantized peer payloads.
  score_gap        every round's MultiKRUM scores against distances of the
                   dequantized payloads in float64, over the scale of the
                   Gram terms the distances are formed from (m x the largest
                   |x_i|^2 + |x_j|^2): the error of the Gram form
                   |x_i|^2 + |x_j|^2 - 2 x_i.x_j lies on that scale, and it
                   stays as the silos' models converge.
  missing          submissions of window rounds that did not land with all
                   their assigned scores.

A traced run traces the device over one round after the warm-up round, and
then opens the window: the profiler's per-op collection slows the host loop
several times, so no span or round time is taken while it runs.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import q8ref
from chipbench.harness import Patches, Run, Spans, Window, rss_bytes

FAULTS = ("frozen_step", "half_batch", "no_exchange", "altered_answer",
          "altered_payload")
TRAIN_STEPS = 3          # steps the training comparison follows
PEERLESS = 1e-3          # leaves whose reference norm is below this share of
                         # the median leaf's move by round-off alone


class WindowClosed(Exception):
    pass


# --------------------------------------------------------------------------- #
# Build
# --------------------------------------------------------------------------- #

def build(cfg: Dict, traffic: Dict, seed: int):
    """The experiment as the program's builder makes it from the seed."""
    from repro.config import FedConfig, replace
    from repro.configs import get_config
    from repro.core import builder

    model_cfg = replace(get_config(cfg["registry"]), **cfg["overrides"])
    for key, want in cfg["model"].items():
        got = getattr(model_cfg, key)
        if got != want:
            raise ValueError(f"{cfg['name']}: the program's {key} is {got!r}, "
                             f"the configuration file says {want!r}")
    fed = FedConfig(rounds=1 << 30, **traffic["fed"])
    make = {"image": builder.build_image_experiment,
            "lm": builder.build_lm_experiment}[traffic["builder"]]
    return make(model_cfg, fed, seed=seed, **traffic["builder_args"]), fed


def work_per_round(orch, fed) -> Dict[str, int]:
    """Samples one round trains and scores, counted from the built data."""
    train = sum((len(c.data["x"]) // c.batch_size) * c.batch_size
                * s.cluster.local_epochs
                for s in orch.silos for c in s.cluster.clients)
    tests = [len(s.cluster.test_data["x"]) for s in orch.silos]
    scored = sum(tests)                       # each silo's self-evaluation
    if fed.scorer in ("accuracy", "loss"):    # each scorer, every peer model
        scored += sum(tests) * (fed.n_silos - 1)
    return {"train_samples": train, "eval_samples": scored}


# --------------------------------------------------------------------------- #
# What the timed path produced, kept for the comparison
# --------------------------------------------------------------------------- #

class Capture:
    def __init__(self, client):
        self.client = client           # the client whose steps are followed
        self.training = False
        self.in_window = False         # past the warm-up round
        self.round = 1                 # the round running
        self.start = None
        self.batches: List[Dict] = []
        self.after: List = []
        self.losses: List[float] = []
        self.published: Dict = {}      # round -> {silo: (q, scales)}
        self.encodes: List = []        # (params encoded, q, scales, n)
        self.merges: List = []         # (round, silo, params before, peers,
        self._peers = None             #  weights, after)
        self.scores: List = []         # (round, [(q, scales)], n, m, scores)


def _host(tree):
    return jax.tree.map(lambda a: np.asarray(a), tree)


def install(p: Patches, cap: Capture, fault: Optional[str]) -> None:
    """Capture (every run) and planted faults (tests and calibration only)."""
    from repro.core import orchestrator as orch_mod
    from repro.fed import client as client_mod
    from repro.fed.aggregator import SiloAggregator

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    make_step = client_mod.make_train_step

    def make_train_step(model, opt_name="sgd", momentum=0.0):
        step, opt = make_step(model, opt_name, momentum)

        def recorded(params, opt_state, batch, lr):
            feed = batch
            if fault == "half_batch":
                feed = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
            new, opt_state, metrics = step(params, opt_state, feed, lr)
            if fault == "frozen_step":
                new = params
            if cap.training and len(cap.after) < TRAIN_STEPS:
                cap.batches.append(_host(batch))
                cap.after.append(_host(new))
                cap.losses.append(float(metrics["loss"]))
            return new, opt_state, metrics
        return recorded, opt

    p.set(client_mod, "make_train_step", make_train_step)

    local_train = cap.client.local_train

    def followed(params, epochs=2):
        if cap.start is None:
            cap.start = _host(params)
            cap.training = True
        try:
            return local_train(params, epochs)
        finally:
            cap.training = False
    p.set(cap.client, "local_train", followed)

    from repro.core.orchestrator import SiloRuntime
    encode = SiloRuntime._encode

    def _encode(self):
        out = encode(self)
        cap.published.setdefault(cap.round, {})[self.silo_id] = (
            out["q"], out["scales"])
        if cap.in_window:
            cap.encodes.append((self.cluster.params, out["q"], out["scales"],
                                int(out["n"])))
        return out
    p.set(SiloRuntime, "_encode", _encode)

    merge = SiloRuntime.pull_and_merge

    def pull_and_merge(self):
        before = self.cluster.params
        cap._peers = None
        out = merge(self)
        if cap.in_window and cap._peers is not None:
            peers, weights = cap._peers
            cap.merges.append((cap.round, self.silo_id, before, peers,
                               weights, self.cluster.params))
        return out
    p.set(SiloRuntime, "pull_and_merge", pull_and_merge)

    apply_vec = SiloAggregator.apply_cross_silo_vec

    def apply_cross_silo_vec(self, own_vec, peers, weights):
        if fault == "altered_payload":
            d = peers[0]
            q = np.array(d.q, np.int8)
            q[0] = -q[0] if q[0] else 1
            peers = [type(d)(d.n, q=q, scales=d.scales, method=d.method)
                     ] + list(peers[1:])
        cap._peers = ([(d.q, d.scales) for d in peers], list(weights))
        if fault == "no_exchange":
            return own_vec
        return apply_vec(self, own_vec, peers, weights)
    p.set(SiloAggregator, "apply_cross_silo_vec", apply_cross_silo_vec)

    mk = orch_mod.multikrum_scores_for_decoded

    def multikrum_scores_for_decoded(decoded, m):
        scores = mk(decoded, m)
        if fault == "altered_answer":
            scores = [scores[0] * 1.1] + list(scores[1:])
        if cap.in_window:
            cap.scores.append((cap.round, [(d.q, d.scales) for d in decoded],
                               int(decoded[0].n), m, list(scores)))
        return scores
    p.set(orch_mod, "multikrum_scores_for_decoded", multikrum_scores_for_decoded)


def install_spans(p: Patches, spans: Spans) -> None:
    """Host spans around the calls into each layer (traced runs only)."""
    from repro.core import orchestrator as orch_mod
    from repro.core.orchestrator import SiloRuntime
    from repro.core.store import StoreNode
    from repro.fed import scorebatch
    from repro.fed.cluster import Cluster

    p.set(Cluster, "train_round", spans.wrap("train", Cluster.train_round))
    for owner, name in ((SiloRuntime, "_encode"), (StoreNode, "put"),
                        (SiloRuntime, "checkpoint")):
        p.set(owner, name, spans.wrap("publish", getattr(owner, name)))
    p.set(scorebatch, "score_round_batch",
          spans.wrap("score", scorebatch.score_round_batch))
    p.set(Cluster, "evaluate", spans.wrap("score", Cluster.evaluate))
    p.set(orch_mod, "multikrum_scores_for_decoded",
          spans.wrap("score", orch_mod.multikrum_scores_for_decoded))
    p.set(SiloRuntime, "pull_and_merge",
          spans.wrap("merge", SiloRuntime.pull_and_merge,
                     sync=lambda args, _: jax.block_until_ready(
                         args[0].cluster.params)))


def install_kernel_calls(p: Patches, run: Run) -> None:
    """Operations and bytes of each kernel call in the traced round, from
    its shapes (traced runs only)."""
    from repro.kernels import ops
    wsum = ops.weighted_sum_q8

    def weighted_sum_q8(q, scales, w, n=None, force="auto"):
        if run.tracing:
            m, n_padded = q.shape
            run.kernel_calls.setdefault("wsum_q8", []).append(
                q8ref.wsum_q8_cost(m, n_padded if n is None else n))
        return wsum(q, scales, w, n, force)
    p.set(ops, "weighted_sum_q8", weighted_sum_q8)


def warm_merge(orch, fed) -> None:
    """Compile what round 2 first runs, the cross-silo merge, at the cell's
    shapes through the program's aggregator (on a throwaway instance, so no
    silo's state moves)."""
    from repro.core.wire import DecodedModel
    from repro.fed.aggregator import SiloAggregator
    from repro.kernels import ops

    silo = orch.silos[0]
    spec = silo.flat_spec()
    own, _ = ops.flatten_pytree(silo.cluster.params, spec)
    n = ops.spec_length(spec)
    peers = [DecodedModel(n, q=np.zeros(q8ref.padded(n), np.int8),
                          scales=np.ones(q8ref.padded(n) // q8ref.TILE,
                                         np.float32), method="int8")
             for _ in range(fed.n_silos - 1)]
    vec = SiloAggregator("warm-up", fed.server_opt).apply_cross_silo_vec(
        own, peers, [1.0] * fed.n_silos)
    jax.block_until_ready(ops.unflatten_pytree(vec, spec))


# --------------------------------------------------------------------------- #
# One run
# --------------------------------------------------------------------------- #

def drive(cfg: Dict, traffic: Dict, seed: int, seconds: float, *,
          traced: bool, compiles, log, t_start: float,
          fault: Optional[str] = None, tracer=None) -> Dict:
    """Set-up, window and capture. ``tracer`` (traced runs) has start() and
    stop() around the traced round, which runs between the warm-up round and
    the window. Returns the run, its counts and the capture; the comparison
    is ``compare``'s."""
    orch, fed = build(cfg, traffic, seed)
    rng = np.random.default_rng(seed)
    silo = orch.silos[int(rng.integers(fed.n_silos))]
    cap = Capture(silo.cluster.clients[int(rng.integers(fed.clients_per_silo))])
    run = Run(chips=1)
    window = Window(seconds)
    run.window = window
    spans = Spans(jax.profiler.TraceAnnotation if traced else None)
    compiles0 = [0]
    warm_rounds = 1
    first = warm_rounds + 1 + int(traced)    # the window's first round
    t_traced = [0.0]

    def open_window():
        window.open(time.perf_counter())
        spans.recording = True

    mark = orch._mark_round

    def on_round(rnd, silo_id=None):
        mark(rnd, silo_id)
        t_end = time.perf_counter()
        cap.round = rnd + 1
        if rnd < warm_rounds:
            return
        if rnd == warm_rounds:
            warm_merge(orch, fed)
            run.setup_s = time.perf_counter() - t_start
            compiles0[0] = compiles.n
            log(f"setup: {run.setup_s:.3f} s, rss {rss_bytes()} B, "
                f"{compiles.n} compiles ({compiles.secs:.1f} s)")
            cap.in_window = True
            if traced:
                tracer.start()
                run.tracing = True
                t_traced[0] = time.perf_counter()
            else:
                open_window()
            return
        if run.tracing:
            tracer.stop()
            run.tracing = False
            log(f"traced round {rnd}: {t_end - t_traced[0]:.6f} s, "
                f"compiles {compiles.n - compiles0[0]}")
            open_window()
            return
        more = window.round_ended(t_end)
        log(f"round {rnd}: {window.walls[-1]:.6f} s, rss {rss_bytes()} B, "
            f"compiles in window {compiles.n - compiles0[0]}")
        if not more:
            cap.in_window = False
            spans.recording = False
            raise WindowClosed()

    with Patches() as p:
        install(p, cap, fault)
        if traced:
            install_spans(p, spans)
            install_kernel_calls(p, run)
        p.set(orch, "_mark_round", on_round)
        try:
            orch.run(fed.rounds)
        except WindowClosed:
            pass
    dev = jax.devices()[0]
    run.peak_hbm_bytes = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    run.spans = spans if traced else None
    work = work_per_round(orch, fed)
    rounds = range(first, first + window.rounds)
    attempted = fed.n_silos * window.rounds
    failed = attempted - sum(
        1 for r in rounds for e in orch.contract.get_round_models(r)
        if set(e.assigned) <= set(e.scores))
    log(f"window: {window.rounds} rounds in {window.window_s:.6f} s "
        f"(overshoot {window.window_s - seconds:.3f} s); compiles in window "
        f"{compiles.n - compiles0[0]}")
    kept = _to_host(cap)
    del orch, silo
    _release()
    return {"run": run, "attempted": attempted, "failed": failed,
            "work": work, "capture": kept, "take_all": fed.agg_policy == "all",
            "lr": traffic["builder_args"]["lr"]}


def _to_host(cap: Capture) -> Dict:
    payload = lambda qs: (np.asarray(qs[0]), np.asarray(qs[1]))
    return {
        "start": cap.start, "batches": cap.batches, "after": cap.after,
        "losses": cap.losses,
        "published": {r: {s: payload(qs) for s, qs in by.items()}
                      for r, by in cap.published.items()},
        "encodes": [(_flat(p), np.asarray(q), np.asarray(s), n)
                    for p, q, s, n in cap.encodes],
        "merges": [(r, silo, _flat(b), [payload(qs) for qs in pe], w, _flat(a))
                   for r, silo, b, pe, w, a in cap.merges],
        "scores": [(r, [payload(qs) for qs in pl], n, m, sc)
                   for r, pl, n, m, sc in cap.scores],
    }


def _release() -> None:
    """Drop the program's process-wide caches of device arrays."""
    import gc
    from repro.fed import scorebatch
    scorebatch._PREPARED.clear()
    scorebatch._EVAL_FNS.clear()
    gc.collect()


# --------------------------------------------------------------------------- #
# The comparison
# --------------------------------------------------------------------------- #

def _flat(tree, dtype=np.float64) -> np.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return np.concatenate([np.ravel(np.asarray(l, np.float32)).astype(dtype)
                           for l in leaves])


def _leaf_norms(tree) -> np.ndarray:
    return np.asarray([np.linalg.norm(np.asarray(l, np.float32).astype(np.float64))
                       for l in jax.tree_util.tree_leaves(tree)])


def _diff(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float32).astype(np.float64)
                        - np.asarray(y, np.float32).astype(np.float64), a, b)


def norm_gap(got, want) -> float:
    """Worst leaf's |norm(got) - norm(want)| over the larger of norm(want)
    and the median leaf's norm; leaves the reference all but leaves still
    (norm under PEERLESS of the median) are left out."""
    med = float(np.median(want))
    keep = want >= PEERLESS * med
    return float(np.max(np.abs(got - want)[keep]
                        / np.maximum(want[keep], med)))


def training_gaps(start, losses, after, ref, lr: float) -> Dict[str, float]:
    r_start, r_losses, r_after = ref
    g = _leaf_norms(_diff(start, after[0])) / lr
    g_ref = _leaf_norms(_diff(r_start, r_after[0])) / lr
    d = _leaf_norms(_diff(after[-1], start))
    d_ref = _leaf_norms(_diff(r_after[-1], r_start))
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(losses, r_losses)),
        "grad_gap": norm_gap(g, g_ref),
        "update_gap": norm_gap(d, d_ref),
    }


def encode_mismatch(x: np.ndarray, q, s, n: int, dtype=np.float32) -> float:
    """Share of elements whose decoded value lies more than a quarter of
    their tile's quantization step from the reference quantizer's (run in
    ``dtype``: float32, or lower for the control). A code off by one is a
    whole step; a scale that differs by a rounding of its own is not."""
    qr, sr = q8ref.quantize(x, dtype)
    step = np.repeat(sr.astype(np.float64), q8ref.TILE)[:n]
    gap = np.abs(q8ref.dequantize(q, s, n) - q8ref.dequantize(qr, sr, n))
    return float(np.mean(gap > 0.25 * step))


def merge_ref(before: np.ndarray, peers, weights, dtype=np.float64):
    """The weighted mean of the silo's params and its peers' payloads, each
    operation rounded to ``dtype``."""
    lo = lambda a: q8ref.rounded(a, dtype)
    w = np.asarray(weights, np.float64)
    w = lo(w / w.sum())
    n = before.shape[0]
    acc = lo(w[0] * lo(before))
    for wi, (q, s) in zip(w[1:], peers):
        acc = lo(acc + lo(wi * q8ref.dequantize(q, s, n, dtype)))
    return acc


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def krum_ref(payloads, n: int, m: int, dtype=np.float64) -> np.ndarray:
    """MultiKRUM scores (negated sums of the m smallest squared distances);
    elementwise work rounded to ``dtype``, sums kept in float64."""
    lo = lambda a: q8ref.rounded(a, dtype)
    x = np.stack([q8ref.dequantize(q, s, n, dtype) for q, s in payloads])
    k = x.shape[0]
    d = np.full((k, k), np.inf)
    for i in range(k):
        for j in range(k):
            if i != j:
                d[i, j] = float(np.sum(lo(lo(x[i] - x[j]) ** 2)))
    m = min(m, k - 1)
    return -np.sort(d, axis=1)[:, :m].sum(axis=1)


def gram_scale(payloads, n: int, m: int) -> float:
    """The scale of the terms a score is formed from: m x the largest
    |x_i|^2 + |x_j|^2 over pairs of the dequantized payloads (float64)."""
    sq = [float(np.sum(q8ref.dequantize(q, s, n) ** 2)) for q, s in payloads]
    m = min(m, len(sq) - 1)
    return m * max(a + b for i, a in enumerate(sq)
                   for j, b in enumerate(sq) if i != j)


def score_gap(got, want, scale: float) -> float:
    return float(np.max(np.abs(np.asarray(got) - want)) / scale)


def _same(a, b) -> bool:
    return all(np.array_equal(np.ravel(x), np.ravel(y)) for x, y in zip(a, b))


def unmatched(consumed, published, take_all: bool) -> int:
    """Consumed payloads that equal no published one, or one already
    matched; with ``take_all``, also the published payloads left out."""
    left = list(published)
    bad = 0
    for pay in consumed:
        hit = next((i for i, pub in enumerate(left) if _same(pay, pub)), None)
        if hit is None:
            bad += 1
        else:
            left.pop(hit)
    return bad + (len(left) if take_all else 0)


def peer_mismatch(c: Dict, take_all: bool) -> int:
    """Merges take the other silos' envelopes of the round before; MultiKRUM
    scores every envelope of its own round."""
    pub = c["published"]
    bad = sum(unmatched(pe, [v for s, v in pub.get(r - 1, {}).items()
                             if s != silo], take_all)
              for r, silo, _, pe, _, _ in c["merges"])
    bad += sum(unmatched(pl, list(pub.get(r, {}).values()), True)
               for r, pl, _, _, _ in c["scores"])
    return bad


def compare(out: Dict, cfg_mod, seed: int) -> Dict[str, float]:
    """The program's numbers: each gap between what the timed rounds
    produced and the plain reference."""
    c = out["capture"]
    numbers = training_gaps(c["start"], c["losses"], c["after"],
                            cfg_mod.sgd_steps(seed, c["batches"], out["lr"]),
                            out["lr"])
    numbers["encode_mismatch"] = max(
        (encode_mismatch(x, q, s, n) for x, q, s, n in c["encodes"]),
        default=1.0)
    numbers["peer_mismatch"] = float(peer_mismatch(c, out["take_all"]))
    numbers["merge_gap"] = max(
        (rel_gap(a, merge_ref(b, pe, w))
         for _, _, b, pe, w, a in c["merges"]), default=1.0)
    numbers["score_gap"] = max(
        (score_gap(sc, krum_ref(pl, n, m), gram_scale(pl, n, m))
         for _, pl, n, m, sc in c["scores"]), default=1.0)
    numbers["missing"] = float(out["failed"])
    return numbers


def control(out: Dict, cfg_mod, seed: int) -> Dict[str, float]:
    """The control: the reference in bfloat16, the precision below the
    configuration's float32, in the program's place on the same inputs (it
    consumes the published payloads as they are: no peer mismatch)."""
    bf16 = jnp.bfloat16
    c = out["capture"]
    lr = out["lr"]
    low = cfg_mod.sgd_steps(seed, c["batches"], lr, dtype=bf16)
    numbers = training_gaps(*low, cfg_mod.sgd_steps(seed, c["batches"], lr), lr)
    numbers["encode_mismatch"] = max(
        encode_mismatch(x, *q8ref.quantize(x, bf16), n, np.float32)
        for x, q, s, n in c["encodes"])
    numbers["peer_mismatch"] = 0.0
    numbers["merge_gap"] = max(
        rel_gap(merge_ref(b, pe, w, bf16), merge_ref(b, pe, w))
        for _, _, b, pe, w, _ in c["merges"])
    numbers["score_gap"] = max(
        score_gap(krum_ref(pl, n, m, bf16), krum_ref(pl, n, m),
                  gram_scale(pl, n, m))
        for _, pl, n, m, _ in c["scores"])
    return numbers
