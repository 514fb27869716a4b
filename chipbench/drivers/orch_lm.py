"""Driver of the program's round engine for a language model at its
published widths: ``SyncOrchestrator.run``, as ``orch`` drives it, with a
capture that fits hundreds of millions of parameters in the host's memory.

Set-up, window, spans and the merge's warm-up are ``orch``'s: its ``build``,
``install_spans``, ``install_kernel_calls``, ``warm_merge`` and comparison
helpers are imported, not copied. What ``orch`` keeps (every published
payload, every merge's params) would not fit; this capture keeps

  * the CID of every envelope published and of every payload that a merge
    or a loss-scoring pass consumed in the window, a consumed payload being
    named by the ``SiloRuntime.fetch`` that returned it;
  * in the window's first round, from one silo and one scorer drawn from
    the seed: the silo's encode and merge over a seeded span of ``SAMPLE``
    elements of the flat model (its params, codes and scales, its peers'
    payloads, the merged float32 vector), the scorer's loss-scoring pass
    whole (payloads, windows, scores), and whether each payload those two
    consumed is, bit for bit, the envelope its owner published;
  * the first three SGD steps of one client drawn from the seed (round 1):
    their batches and losses, and the params before and after them.

The sample's params are read back where the program has just read them back
itself (each round's checkpoint), so that they add no transfer to the
window; the merged vector is the one the sample adds.

It counts the tokens each round trains and scores, and in traced runs it
switches the program's wall on (``FedConfig.obs``) and records every
``dequantize_batch`` call of the traced round. The store's retention
counters then give what the store nodes hold when the window closes; a
program that does not declare them cannot bound its store, and the run
stops at once. Every round's end checks the process's RSS and stops with an
error past 85% of the host's memory.

What ``correct`` compares, after the program's state is released, against
the configuration module's plain float32 reference, on the chip:

  loss_gap         each of the three steps' loss against the reference's
                   three steps from the same params on the same batches,
                   relative.
  grad_gap         the program's gradient at those params on the first batch
                   (its own ``jax.value_and_grad`` of the model's loss)
                   against the reference's, by worst leaf: |g - g_ref| over
                   |g_ref| (L2), on the leaves whose reference gradient is at
                   least ``orch.PEERLESS`` of the median leaf's.
  update_gap       the change of the params over the three steps against the
                   reference's, whose params are stored in bfloat16 between
                   steps as the program's are, by worst leaf's norm
                   (``orch.norm_gap``).
  encode_mismatch  the sample's codes against the reference quantizer
                   (``orch.encode_mismatch``).
  peer_mismatch    consumed payloads that match no published envelope, or
                   repeat one (``orch.unmatched``): by CID in every window
                   round, and bit for bit in the sample. A merge in round r
                   takes the other silos' envelopes of round r - 1 (all of
                   them under policy ``all``); a scorer, other silos'
                   envelopes of round r.
  merge_gap        the sample's merged float32 vector against
                   ``orch.merge_ref`` in float64 (``orch.rel_gap``'s form).
  loss_score_gap   each of the sample scorer's loss scores against minus the
                   reference's mean loss over the scorer's windows of the
                   dequantized payload, relative.
  missing          submissions of window rounds that did not land with all
                   their assigned scores.
"""
from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import dequant_cost, harness, q8ref
from chipbench.harness import (Patches, Run, Spans, Window, mem_total_bytes,
                               rss_bytes)

ORCH = harness.load_module("drivers", "orch")
FAULTS = ORCH.FAULTS + ("no_qk_norm",)
RETENTION = ("store_bytes_in", "store_bytes_dropped")
RSS_SHARE = 0.85          # of the host's memory: past it the run stops
SAMPLE = 512 * q8ref.BLOCK  # elements of the sample's span (about an eighth
                            # of qwen3-1.7b at 4 layers)
CHUNK = 64 * q8ref.BLOCK  # elements per host pass of the flat comparisons
THREADS = min(4, os.cpu_count() or 1)   # NumPy releases the GIL on chunks
CGROUP_LIMITS = ("/sys/fs/cgroup/memory.max",
                 "/sys/fs/cgroup/memory/memory.limit_in_bytes")


def require_retention() -> None:
    """Stop at once where the program does not declare the store's
    retention counters: such a store keeps every payload, and this cell's
    rounds (10.9 GB each) outgrow the host within a window."""
    from repro.obs.metrics import SCHEMAS
    missing = [k for k in RETENTION if k not in SCHEMAS.get("wall", {})]
    if missing:
        raise SystemExit(
            f"orch_lm: the program declares no {' or '.join(missing)} in its "
            f"wall schema (repro.obs.metrics): its store has no bounded "
            f"retention, which this cell needs to fit the host")


def host_memory() -> int:
    """The host's memory: MemTotal, or the process's cgroup limit where one
    is set below it."""
    limits = [mem_total_bytes()]
    for path in CGROUP_LIMITS:
        try:
            with open(path) as f:
                limits.append(int(f.read().strip()))
        except (OSError, ValueError):
            pass
    return min(limits)


def dims_of(model_cfg) -> Dict:
    """The sizes the reference reads, from the program's model config."""
    return {"n_heads": model_cfg.n_heads, "n_kv_heads": model_cfg.n_kv_heads,
            "head_dim": model_cfg.resolved_head_dim,
            "vocab_size": model_cfg.vocab_size,
            "rope_theta": float(model_cfg.rope_theta),
            "eps": float(model_cfg.norm_eps)}


def work_per_round(orch, fed) -> Dict[str, int]:
    """Tokens one round trains (every client's steps) and scores (each
    silo's self-evaluation, and each scorer's windows for every model it is
    assigned: n // 2 + 1 scorers a model, never its owner)."""
    seq = orch.silos[0].cluster.test_data["seq_len"]
    train = sum(c.data["steps_per_epoch"] * c.batch_size * c.data["seq_len"]
                * s.cluster.local_epochs
                for s in orch.silos for c in s.cluster.clients)
    windows = [len(range(0, min(len(s.cluster.test_data["tokens"]) - seq - 1,
                                4 * seq), seq)) for s in orch.silos]
    n = fed.n_silos
    per_scorer = min(n - 1, n // 2 + 1)     # models a silo scores a round
    return {"train_tokens": train, "seq_len": seq,
            "eval_tokens": sum(windows) * seq * (1 + per_scorer)}


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def flat_span(tree, lo: int, hi: int) -> np.ndarray:
    """Elements [lo, hi) of the tree's leaves laid end to end (the program's
    flatten order), as float32 on the host; only the span is copied."""
    out, off = [], 0
    for leaf in jax.tree_util.tree_leaves(tree):
        a = np.asarray(leaf).reshape(-1)
        s, e = max(lo - off, 0), min(hi - off, a.shape[0])
        if s < e:
            out.append(a[s:e].astype(np.float32))
        off += a.shape[0]
    return np.concatenate(out)


def payload_span(q, s, lo: int, hi: int):
    """Copies of the codes and scales that hold elements [lo, hi) (lo on a
    block's start)."""
    end = lo + q8ref.padded(hi - lo)
    return (np.array(q[lo:end]),
            np.array(s[lo // q8ref.TILE:end // q8ref.TILE]))


# --------------------------------------------------------------------------- #
# What the timed path produced, kept for the comparison
# --------------------------------------------------------------------------- #

class Capture:
    def __init__(self, client, sample_round: int, sample_silo: str,
                 scorer: str, span):
        self.client = client           # the client whose steps are followed
        self.training = False
        self.start = None
        self.batches: List[Dict] = []
        self.after = None              # the params after the last step
        self.losses: List[float] = []
        self.in_window = False         # past the warm-up round
        self.round = 1                 # the round running
        self.sample_round = sample_round
        self.sample_silo = sample_silo  # whose encode and merge are kept
        self.scorer = scorer           # whose loss-scoring pass is kept
        self.span = span               # (lo, hi) of the sample's elements
        self.published: Dict = {}      # round -> {silo: cid}
        self.bits: Dict = {}           # cid -> (q, s), until the sample's use
        self.bits_bad = 0              # the sample's payloads unmatched
        self.bits_checked = 0          # the sample's consumers checked
        self.named: Dict = {}          # id(payload) -> (payload, cid), this
        self.consumed: List = []       # round; (round, silo, kind, [cid])
        self.encode = None             # (params, q, scales) over the span
        self.before = None             # the sample silo's params pre-merge
        self.merge = None              # (before, [(q, s)], weights, vec)
        self.score = None              # ([(q, s)], (tok, tgt), scores)

    def name(self, payload) -> Optional[str]:
        hit = self.named.get(id(payload))
        return hit[1] if hit is not None and hit[0] is payload else None

    def keeps(self, rnd: int, silo: str) -> bool:
        """Whether an envelope published by ``silo`` in ``rnd`` is one the
        sample's merge (round before) or scorer (same round) consumes."""
        return ((rnd == self.sample_round - 1 and silo != self.sample_silo)
                or (rnd == self.sample_round and silo != self.scorer))

    def check_bits(self, payloads, rnd: int, silo: str, take_all: bool):
        """The sample's consumed payloads against the envelopes the other
        silos published in ``rnd``, bit for bit; those are then let go."""
        pub = [self.bits.pop(cid) for s, cid in
               self.published.get(rnd, {}).items()
               if s != silo and cid in self.bits]
        self.bits_bad += ORCH.unmatched(payloads, pub, take_all)
        self.bits_checked += 1


def install(p: Patches, cap: Capture, fault: Optional[str],
            take_all: bool) -> None:
    """Capture (every run) and planted faults (tests and calibration only)."""
    from repro.core.orchestrator import SiloRuntime
    from repro.core.store import StoreNode
    from repro.fed import client as client_mod
    from repro.fed import scorebatch
    from repro.fed.aggregator import SiloAggregator

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    lo, hi = cap.span
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
            if cap.training and len(cap.batches) < ORCH.TRAIN_STEPS:
                cap.batches.append(_host(batch))
                cap.losses.append(float(metrics["loss"]))
                cap.after = _host(new)
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

    put = StoreNode.put

    def put_(self, obj, *, pin=True):
        cid = put(self, obj, pin=pin)
        if isinstance(obj, dict) and "__wire__" in obj:
            cap.published.setdefault(cap.round, {})[self.node_id] = cid
            if cap.keeps(cap.round, self.node_id):
                cap.bits[cid] = (obj["q"], obj["scales"])
        return cid
    p.set(StoreNode, "put", put_)

    encode = SiloRuntime._encode

    def _encode(self):
        out = encode(self)
        if cap.round == cap.sample_round and self.silo_id == cap.sample_silo:
            # the params join them at the round's end (``drive``)
            cap.encode = (None,) + payload_span(out["q"], out["scales"],
                                                lo, hi)
        return out
    p.set(SiloRuntime, "_encode", _encode)

    merge = SiloRuntime.pull_and_merge

    def pull_and_merge(self):
        if cap.round == cap.sample_round and self.silo_id == cap.sample_silo:
            cap.before = flat_span(self.cluster.params, lo, hi)
        return merge(self)
    p.set(SiloRuntime, "pull_and_merge", pull_and_merge)

    fetch = SiloRuntime.fetch

    def fetch_(self, cid):
        dm = fetch(self, cid)
        cap.named[id(dm)] = (dm, cid)
        return dm
    p.set(SiloRuntime, "fetch", fetch_)

    apply_vec = SiloAggregator.apply_cross_silo_vec

    def apply_cross_silo_vec(self, own_vec, peers, weights):
        if fault == "altered_payload":
            d = peers[0]
            q = np.array(d.q, np.int8)
            q[0] = -q[0] if q[0] else 1
            peers = [type(d)(d.n, q=q, scales=d.scales, method=d.method)
                     ] + list(peers[1:])
        out = own_vec if fault == "no_exchange" \
            else apply_vec(self, own_vec, peers, weights)
        if cap.in_window:
            cap.consumed.append((cap.round, self.silo_id, "merge",
                                 [cap.name(d) for d in peers]))
            if cap.round == cap.sample_round \
                    and self.silo_id == cap.sample_silo:
                payloads = [(d.q, d.scales) for d in peers]
                cap.check_bits(payloads, cap.round - 1, self.silo_id,
                               take_all)
                cap.merge = (cap.before,
                             [payload_span(q, s, lo, hi)
                              for q, s in payloads],
                             list(weights), np.asarray(out[lo:hi]))
        return out
    p.set(SiloAggregator, "apply_cross_silo_vec", apply_cross_silo_vec)

    score = scorebatch.score_round_batch

    def score_round_batch(cluster, decoded, spec, *, method="accuracy"):
        scores = score(cluster, decoded, spec, method=method)
        if fault == "altered_answer":
            scores = [scores[0] * 1.1] + list(scores[1:])
        if cap.in_window:
            cap.consumed.append((cap.round, cluster.silo_id, "score",
                                 [cap.name(d) for d in decoded]))
            if cap.round == cap.sample_round \
                    and cluster.silo_id == cap.scorer:
                payloads = [(d.q, d.scales) for d in decoded]
                cap.check_bits(payloads, cap.round, cluster.silo_id, False)
                windows = scorebatch.get_scorer(cluster)._prep()["args"]
                cap.score = (payloads, tuple(np.asarray(a) for a in windows),
                             list(scores))
        return scores
    p.set(scorebatch, "score_round_batch", score_round_batch)


def install_dequant_calls(p: Patches, run: Run) -> None:
    """Operations and bytes of each ``dequantize_batch`` call in the traced
    round, from its shapes (traced runs only): the scoring engine's one
    program from the payloads to the stacked leaves runs one per call."""
    from repro.fed import scorebatch
    from repro.kernels import ops
    stacked = scorebatch._dequantize_stacked

    def _dequantize_stacked(q, scales, spec):
        if run.tracing:
            out = np.dtype(scorebatch._leaf_dtype(spec)).itemsize
            run.kernel_calls.setdefault("dequantize_batch", []).append(
                dequant_cost.dequantize_batch_cost(
                    q.shape[0], ops.spec_length(spec), out))
        return stacked(q, scales, spec)
    p.set(scorebatch, "_dequantize_stacked", _dequantize_stacked)


# --------------------------------------------------------------------------- #
# One run
# --------------------------------------------------------------------------- #

def drive(cfg: Dict, traffic: Dict, seed: int, seconds: float, *,
          traced: bool, compiles, log, t_start: float,
          fault: Optional[str] = None, tracer=None) -> Dict:
    """Set-up, window and capture, as ``orch.drive`` runs them. Returns the
    run, its counts and the capture; the comparison is ``compare``'s."""
    require_retention()
    from repro.config import ObsConfig, replace
    from repro.kernels import ops
    from repro.obs import wall

    fed_args = dict(traffic["fed"])
    if traced:
        fed_args["obs"] = ObsConfig(wall=True)
    orch, fed = ORCH.build(cfg, dict(traffic, fed=fed_args), seed)
    model = orch.silos[0].cluster.model
    if fault == "no_qk_norm":
        # the program's attention without its per-head norm of q and k (the
        # params keep q_norm and k_norm, which it then leaves untouched)
        model.cfg = replace(model.cfg, qk_norm=False)
    rng = np.random.default_rng(seed)
    silo = orch.silos[int(rng.integers(fed.n_silos))]
    client = silo.cluster.clients[int(rng.integers(fed.clients_per_silo))]
    n = ops.spec_length(silo.flat_spec())
    blocks = -(-n // q8ref.BLOCK) - SAMPLE // q8ref.BLOCK
    lo = int(rng.integers(blocks + 1)) * q8ref.BLOCK if blocks > 0 else 0
    warm_rounds = 1
    first = warm_rounds + 1 + int(traced)    # the window's first round
    cap = Capture(client, first,
                  orch.silos[int(rng.integers(fed.n_silos))].silo_id,
                  orch.silos[int(rng.integers(fed.n_silos))].silo_id,
                  (lo, min(n, lo + SAMPLE)))
    run = Run(chips=1)
    window = Window(seconds)
    run.window = window
    spans = Spans(jax.profiler.TraceAnnotation if traced else None)
    compiles0 = [0]
    t_traced = [0.0]
    limit = RSS_SHARE * host_memory()

    def open_window():
        window.open(time.perf_counter())
        spans.recording = True

    def held() -> Optional[int]:
        t = wall.totals()
        return t["store_bytes_in"] - t["store_bytes_dropped"] if t else None

    silos = {s.silo_id: s for s in orch.silos}
    seen = [wall.totals()]

    def status(rss: int) -> str:
        """RSS, what the store holds and, with the wall on, the round's
        seconds in the program's spans."""
        now = wall.totals()
        spent = {k[:-2]: round(v, 3)
                 for k, v in wall.diff(seen[0], now).items()
                 if k.endswith("_s") and v > 0.05}
        seen[0] = now
        return f"rss {rss} B, store held {held()} B, wall {spent}"

    mark = orch._mark_round

    def on_round(rnd, silo_id=None):
        mark(rnd, silo_id)
        t_end = time.perf_counter()
        cap.round = rnd + 1
        cap.named.clear()
        if rnd == cap.sample_round and cap.encode is not None:
            # the params the silo encoded, as its checkpoint read them back
            params = silos[cap.sample_silo].cluster.params
            cap.encode = (flat_span(params, *cap.span),) + cap.encode[1:]
        rss = rss_bytes()
        if rnd == warm_rounds:
            ORCH.warm_merge(orch, fed)
            # the sample's slice of the merged vector, compiled here
            jax.block_until_ready(
                jnp.zeros((n,), jnp.float32)[cap.span[0]:cap.span[1]])
            run.setup_s = time.perf_counter() - t_start
            compiles0[0] = compiles.n
            log(f"setup: {run.setup_s:.3f} s, {status(rss)}, {compiles.n} "
                f"compiles ({compiles.secs:.1f} s)")
            cap.in_window = True
            if traced:
                tracer.start()
                run.tracing = True
                t_traced[0] = time.perf_counter()
            else:
                open_window()
        elif run.tracing:
            tracer.stop()
            run.tracing = False
            log(f"traced round {rnd}: {t_end - t_traced[0]:.6f} s, "
                f"{status(rss)}, compiles {compiles.n - compiles0[0]}")
            open_window()
        elif rnd > warm_rounds:
            more = window.round_ended(t_end)
            log(f"round {rnd}: {window.walls[-1]:.6f} s, {status(rss)}, "
                f"compiles in window {compiles.n - compiles0[0]}")
            if not more:
                cap.in_window = False
                spans.recording = False
                run.store_bytes_held = held()
                raise ORCH.WindowClosed()
        if rss > limit:
            raise RuntimeError(
                f"orch_lm: RSS {rss} B after round {rnd} passed "
                f"{RSS_SHARE:.0%} of the host's {host_memory()} B")

    with Patches() as p:
        install(p, cap, fault, fed.agg_policy == "all")
        if traced:
            ORCH.install_spans(p, spans)
            ORCH.install_kernel_calls(p, run)
            install_dequant_calls(p, run)
        p.set(orch, "_mark_round", on_round)
        try:
            orch.run(fed.rounds)
        except ORCH.WindowClosed:
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
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        silo.cluster.params)
    cap.named.clear()
    cap.bits.clear()
    # the last references to the program's state
    del orch, silo, silos, mark, on_round
    ORCH._release()
    log(f"released: rss {rss_bytes()} B")
    kept = {k: getattr(cap, k) for k in (
        "start", "batches", "after", "losses", "published", "consumed",
        "bits_bad", "bits_checked", "encode", "merge", "score")}
    return {"run": run, "attempted": attempted, "failed": failed,
            "work": work, "capture": kept, "take_all": fed.agg_policy == "all",
            "lr": traffic["builder_args"]["lr"], "like": like,
            "dims": dims_of(model.cfg), "model": model}


# --------------------------------------------------------------------------- #
# The comparison
# --------------------------------------------------------------------------- #

@jax.jit
def _leaf_norms(a, b):
    """L2 norm of each leaf of a - b (b: None for a alone), in float32."""
    f32 = lambda x: x.astype(jnp.float32)
    la = jax.tree_util.tree_leaves(a)
    lb = [f32(y) for y in jax.tree_util.tree_leaves(b)] if b is not None \
        else [0.0] * len(la)
    return jnp.stack([jnp.linalg.norm(f32(x) - y) for x, y in zip(la, lb)])


def program_grad(model, params, batch):
    """The program's own gradient of its model's loss, on the device."""
    vg = jax.jit(jax.value_and_grad(lambda p, b: model.loss(p, b)[0]))
    return vg(jax.device_put(params), jax.device_put(batch))[1]


def leaf_gap(diff, ref) -> float:
    """Worst leaf's |got - want| / |want| from per-leaf norms, over leaves
    whose reference norm is at least ``orch.PEERLESS`` of the median
    leaf's."""
    keep = ref >= ORCH.PEERLESS * float(np.median(ref))
    return float(np.max(diff[keep] / ref[keep]))


def training_gaps(out: Dict, cfg_mod, low=None) -> Dict[str, float]:
    """loss, grad and update gaps of the program's three steps (with
    ``low``, of the reference computed in that precision in its place)
    against the reference's, the trees compared on the device."""
    c = out["capture"]
    if not c["batches"] or c["after"] is None:
        return {"loss_gap": 1.0, "grad_gap": 1.0, "update_gap": 1.0}
    lr, dims, batches = out["lr"], out["dims"], c["batches"]
    r_losses, r_after, r_grad = cfg_mod.sgd_steps(c["start"], batches, lr,
                                                  dims)
    if low is None:
        losses = c["losses"]
        grad = program_grad(out["model"], c["start"], batches[0])
        after = jax.device_put(c["after"])
    else:
        losses, after, grad = cfg_mod.sgd_steps(c["start"], batches, lr, dims,
                                                low)
    start = jax.device_put(c["start"])
    norms = lambda a, b=None: np.asarray(_leaf_norms(a, b), np.float64)
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(losses, r_losses)),
        "grad_gap": leaf_gap(norms(grad, r_grad), norms(r_grad)),
        "update_gap": ORCH.norm_gap(norms(after, start),
                                    norms(r_after, start)),
    }


def _chunks(n: int):
    """[lo, hi) slices of whole kernel blocks, and the payload's matching
    padded slice end."""
    for lo in range(0, n, CHUNK):
        hi = min(n, lo + CHUNK)
        yield lo, hi, lo + q8ref.padded(hi - lo)


def _over_chunks(fn, n: int) -> List:
    """``fn(lo, hi, end)`` of every chunk, on a few threads."""
    with ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(lambda c: fn(*c), _chunks(n)))


def _slice(qs, lo: int, end: int):
    q, s = qs
    return q[lo:end], s[lo // q8ref.TILE:end // q8ref.TILE]


def encode_mismatch(x, q, s, low=None) -> float:
    """``orch.encode_mismatch`` over chunks, as the share of all of x; with
    ``low``, the reference quantizer in that precision is what is judged."""
    n = x.shape[0]

    def bad(lo, hi, end):
        qs = q8ref.quantize(x[lo:hi], low) if low is not None \
            else _slice((q, s), lo, end)
        return ORCH.encode_mismatch(x[lo:hi], *qs, hi - lo) * (hi - lo)
    return sum(_over_chunks(bad, n)) / n


def merge_gap(before, peers, weights, after=None, low=None) -> float:
    """``orch.rel_gap`` of ``after`` (or of ``orch.merge_ref`` in ``low``)
    against ``orch.merge_ref`` in float64, over chunks."""
    def extremes(lo, hi, end):
        pe = [_slice(qs, lo, end) for qs in peers]
        want = ORCH.merge_ref(before[lo:hi], pe, weights)
        got = after[lo:hi] if low is None \
            else ORCH.merge_ref(before[lo:hi], pe, weights, low)
        return float(np.max(np.abs(got - want))), float(np.max(np.abs(want)))
    worst, top = zip(*_over_chunks(extremes, before.shape[0]))
    return max(worst) / max(top)


def _payload_params(qs, like):
    """A payload dequantized (q times its tile's scale, float32) into the
    params' structure."""
    q, s = qs
    leaves, treedef = jax.tree_util.tree_flatten(like)
    n = sum(int(np.prod(a.shape)) for a in leaves)
    vec = (np.asarray(q, np.float32).reshape(-1, q8ref.TILE)
           * np.asarray(s, np.float32)[:, None]).reshape(-1)[:n]
    parts, off = [], 0
    for leaf in leaves:
        size = int(np.prod(leaf.shape))
        parts.append(vec[off:off + size].reshape(leaf.shape))
        off += size
    return jax.tree_util.tree_unflatten(treedef, parts)


def reference_losses(out: Dict, cfg_mod, low=None) -> List[float]:
    """The reference's mean loss of each of the sample scorer's payloads on
    its windows (kept in ``out``, which the control reads too)."""
    key = "reference_losses" if low is None else "reference_losses_low"
    if key not in out:
        payloads, (tok, tgt), _ = out["capture"]["score"]
        out[key] = [cfg_mod.loss_of(_payload_params(qs, out["like"]), tok,
                                    tgt, out["dims"], low)
                    for qs in payloads]
    return out[key]


def peer_mismatch(c: Dict, take_all: bool) -> int:
    """By CID for every consumption in the window, plus the sample's bit
    for bit count (and one for each of its two consumers not seen)."""
    pub = c["published"]
    others = lambda r, silo: [(cid,) for s, cid in pub.get(r, {}).items()
                              if s != silo]
    bad = c["bits_bad"] + max(0, 2 - c["bits_checked"])
    for r, silo, kind, cids in c["consumed"]:
        mine = [(x,) for x in cids]
        if kind == "merge":
            bad += ORCH.unmatched(mine, others(r - 1, silo), take_all)
        else:
            bad += ORCH.unmatched(mine, others(r, silo), False)
    return bad


def compare(out: Dict, cfg_mod, seed: int) -> Dict[str, float]:
    """The program's numbers: each gap between what the timed rounds
    produced and the plain reference."""
    c = out["capture"]
    numbers = training_gaps(out, cfg_mod)
    numbers["encode_mismatch"] = 1.0
    if c["encode"] is not None and c["encode"][0] is not None:
        numbers["encode_mismatch"] = encode_mismatch(*c["encode"])
    numbers["peer_mismatch"] = float(peer_mismatch(c, out["take_all"]))
    numbers["merge_gap"] = merge_gap(*c["merge"]) if c["merge"] is not None \
        else 1.0
    numbers["loss_score_gap"] = 1.0
    if c["score"] is not None:
        numbers["loss_score_gap"] = max(
            abs(sc + l) / abs(l)
            for sc, l in zip(c["score"][2], reference_losses(out, cfg_mod)))
    numbers["missing"] = float(out["failed"])
    return numbers


def control(out: Dict, cfg_mod, seed: int) -> Dict[str, float]:
    """The control: the reference with the inputs of every matrix product
    (and the quantizer's and the merge's arithmetic) in float8_e4m3fn, the
    precision below the configuration's bfloat16, in the program's place on
    the same inputs (it consumes the published payloads as they are: no
    peer mismatch)."""
    f8 = jnp.float8_e4m3fn
    c = out["capture"]
    numbers = training_gaps(out, cfg_mod, f8)
    x, q, s = c["encode"]
    numbers["encode_mismatch"] = encode_mismatch(x, q, s, f8)
    numbers["peer_mismatch"] = 0.0
    before, peers, w, _ = c["merge"]
    numbers["merge_gap"] = merge_gap(before, peers, w, low=f8)
    numbers["loss_score_gap"] = max(
        abs(a - b) / abs(b) for a, b in zip(reference_losses(out, cfg_mod, f8),
                                            reference_losses(out, cfg_mod)))
    return numbers
