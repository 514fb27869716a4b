"""repro.obs.wall: the program's wall-clock spans and counters.

Self time under nesting on a fake clock, counters and snapshot differences,
the off path (no clock read, no annotation, no totals) over a whole round,
the on path's annotations, the registry's adoption, and the exact counts of
host syncs and H2D bytes that a small federated run makes.
"""
import jax
import numpy as np
import pytest

from repro.config import FedConfig, ObsConfig
from repro.configs import get_config
from repro.kernels import ops, quant
from repro.obs import Observability, wall
from repro.obs.metrics import SCHEMAS, WALL_SPANS


@pytest.fixture(autouse=True)
def wall_off_after():
    yield
    wall.switch(False)


class Clock:
    """perf_counter stand-in: returns the given times in order."""

    def __init__(self, *times):
        self.times = list(times)

    def __call__(self):
        return self.times.pop(0)


class Annotations:
    """TraceAnnotation stand-in that records each one opened and closed."""

    def __init__(self):
        self.opened, self.closed = [], []
        rec = self

        class Ann:
            def __init__(self, name, **meta):
                self.name, self.meta = name, meta

            def __enter__(self):
                rec.opened.append((self.name, self.meta))

            def __exit__(self, *exc):
                rec.closed.append(self.name)
        self.cls = Ann


def _forbid(*_, **__):
    raise AssertionError("the wall is off: nothing may read the clock or "
                         "open an annotation")


def test_self_time_is_the_span_less_its_nested_spans(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotations().cls)
    # merge 0..10 holds fetch 1..4 and a second fetch 5..6
    monkeypatch.setattr(wall, "perf_counter", Clock(0.0, 1.0, 4.0, 5.0, 6.0,
                                                    10.0))
    w = wall.switch(True)
    with wall.span("merge"):
        with wall.span("exchange.fetch"):
            pass
        with wall.span("exchange.fetch"):
            pass
    t = wall.totals()
    assert t["merge_s"] == pytest.approx(6.0) and t["merge_n"] == 1
    assert t["exchange.fetch_s"] == pytest.approx(4.0)
    assert t["exchange.fetch_n"] == 2
    assert w._open == []


def test_counters_and_the_difference_of_two_snapshots(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotations().cls)
    wall.switch(True)
    wall.count("host_syncs")
    wall.count("h2d_bytes", 100)
    before = wall.totals()
    wall.count("host_syncs", 2)
    wall.count("h2d_bytes", 28)
    with wall.span("train.wait"):
        pass
    after = wall.totals()
    assert set(after) == set(SCHEMAS["wall"])
    d = wall.diff(before, after)
    assert d["host_syncs"] == 2 and d["h2d_bytes"] == 28
    assert d["train.wait_n"] == 1 and d["train.feed_n"] == 0
    # to_host counts device values only
    wall.to_host(np.ones(3))
    assert wall.totals()["host_syncs"] == after["host_syncs"]
    host = wall.to_host(jax.numpy.ones(3), np.float64)
    assert host.dtype == np.float64
    assert wall.totals()["host_syncs"] == after["host_syncs"] + 1


def test_a_span_is_an_annotation_with_its_metadata(monkeypatch):
    rec = Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.cls)
    wall.switch(True)
    with wall.span("publish.encode", round=3, silo="silo1"):
        with wall.span("train.feed"):
            pass
    assert rec.opened == [("unifyfl:publish.encode",
                           {"round": 3, "silo": "silo1"}),
                          ("unifyfl:train.feed", {})]
    assert rec.closed == ["unifyfl:train.feed", "unifyfl:publish.encode"]


def test_only_declared_spans_open():
    wall.switch(True)
    with pytest.raises(KeyError):
        wall.span("train.everything")
    assert {f"{s}_s" for s in WALL_SPANS} <= set(SCHEMAS["wall"])


def test_observability_switches_the_wall_and_adopts_its_totals():
    obs = Observability(ObsConfig(wall=True))
    assert obs.wall is wall.active() and obs.wall.enabled
    assert not obs.enabled              # the simulated tracer stays off
    wall.count("host_syncs", 5)
    assert obs.registry.flat()["wall/-/host_syncs"] == 5
    off = Observability()
    assert off.wall is wall.NULL_WALL is wall.active()
    assert not any(k.startswith("wall/") for k in off.registry.flat())
    assert wall.totals() == {}


def _build(obs: ObsConfig, rounds: int = 2):
    from repro.core.builder import build_image_experiment
    fed = FedConfig(n_silos=3, clients_per_silo=2, rounds=rounds,
                    local_epochs=1, scorer="multikrum", compression="int8",
                    obs=obs)
    return build_image_experiment(get_config("paper-cnn"), fed, n_train=240,
                                  n_test=90, seed=3)


def test_off_a_round_reads_no_clock_and_opens_no_annotation(monkeypatch):
    monkeypatch.setattr(wall, "perf_counter", _forbid)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _forbid)
    orch = _build(ObsConfig())
    orch.run(2)
    assert wall.active() is wall.NULL_WALL and wall.totals() == {}
    assert orch.ledger.verify()


def test_a_run_counts_its_host_syncs_and_h2d_bytes():
    """Sync int8 MultiKRUM, 3 silos x 2 clients, one epoch, two rounds:
    every count follows from the shards and the model's shapes."""
    rounds = 2
    orch = _build(ObsConfig(wall=True), rounds)
    orch.run(rounds)
    t = orch.obs.registry.snapshot()["wall"]["-"]
    clients = [c for s in orch.silos for c in s.cluster.clients]
    steps = rounds * sum(len(c.data["x"]) // c.batch_size for c in clients)
    leaves = jax.tree_util.tree_leaves(orch.silos[0].cluster.params)
    n_silos = len(orch.silos)
    # per round: a self-evaluation and an envelope (q, scales) per silo,
    # one MultiKRUM transfer, a checkpoint of every leaf per silo
    per_round = n_silos * (1 + 2 + len(leaves)) + 1
    assert t["host_syncs"] == steps + rounds * per_round
    assert t["train.wait_n"] == t["train.dispatch_n"] == steps
    # the feed's last call per client and round finds the shard spent
    assert t["train.feed_n"] == steps + rounds * len(clients)
    bs = clients[0].batch_size
    x = clients[0].data["x"]
    batch = bs * int(np.prod(x.shape[1:])) * 4 + bs * 4 + 4  # f32, i32, lr
    n_padded = quant.padded(ops.spec_length(orch.silos[0].flat_spec()))
    payload = n_padded + n_padded // quant.TILE * 4   # int8 codes + scales
    # merges from round 2 (2 peers each), MultiKRUM every round (3 models)
    payloads = (rounds - 1) * n_silos * (n_silos - 1) + rounds * n_silos
    assert t["h2d_bytes"] == steps * batch + payloads * payload
    for name in WALL_SPANS:
        assert t[f"{name}_s"] > 0 and t[f"{name}_n"] > 0, name
    assert t["exchange.fetch_n"] == payloads
