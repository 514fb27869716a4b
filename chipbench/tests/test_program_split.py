"""``program_split``: the program's wall spans read back from a profiler
trace recorded on the CPU, the idle attribution on hand-made events, and
the cell's driven run at smoke size with the wall on and off, whose counts
follow exactly from the shards and the model's shapes."""
import importlib.util
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import harness, trace
from chipbench.tests import smoke
from chipbench.trace import Event

_spec = importlib.util.spec_from_file_location(
    "chipbench_program_split", harness.HERE / "program_split.py")
split = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(split)


def _record(tmp_path):
    """A trace of one wall span inside the harness's window span."""
    from repro.obs import wall
    wall.switch(True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("chipbench:window"):
            with wall.span("train.feed", round=2, silo="silo0"):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
        spent = wall.totals()["train.feed_s"]
        wall.switch(False)
    return str(sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]), spent


def test_a_recorded_trace_holds_the_program_span_apart_from_the_harness(
        tmp_path):
    path, spent = _record(tmp_path)
    spans = split.load_spans(path)
    assert [s.name for s in spans] == ["train.feed"]
    # the annotation holds the timed part, and the clock reads around it
    assert 0.002 <= spent <= spans[0].dur <= spent + 1e-3
    tr = trace.load(path)
    # the harness's reading of the same file is as it was: its own spans
    assert [s.name for s in tr.spans] == ["window"]
    lo, hi = tr.window()
    assert lo <= spans[0].start and spans[0].end <= hi


def test_idle_goes_to_the_innermost_program_span_the_host_was_in():
    spans = [Event("train.feed", 0.0, 1.0), Event("train.dispatch", 1.0, 0.5),
             Event("train.wait", 1.5, 2.5), Event("merge", 5.0, 3.0),
             Event("exchange.fetch", 5.5, 1.0), Event("window", 0.0, 9.0)]
    ops = [Event("op", 1.6, 0.4), Event("op", 6.6, 0.1)]
    idle = split.idle_by_span(ops, spans, 0.0, 9.0)
    # the gap 0..1.6 runs through a feed, a dispatch and the start of a
    # wait; 2.0..6.6 through the wait's end, no span, the merge and its
    # fetch; 6.7..9 through the merge's end and no span
    assert idle == pytest.approx({"train.feed": 1.0, "train.dispatch": 0.5,
                                  "train.wait": 2.1, "other": 2.0,
                                  "merge": 0.5 + 0.1 + 1.3,
                                  "exchange.fetch": 1.0})
    assert sum(idle.values()) == pytest.approx(9.0 - trace.busy_s(ops, 0, 9))
    assert split.innermost(spans[3:5], 4.0, 9.0) == [
        (4.0, 5.0, "other"), (5.0, 5.5, "merge"),
        (5.5, 6.5, "exchange.fetch"), (6.5, 8.0, "merge"),
        (8.0, 9.0, "other")]


def _measure(wall_on: bool):
    _, _, cfg, traffic, _ = smoke.parts()
    tracer = smoke.run_module().Tracer(jax)
    try:
        return split.measure(cfg, traffic, smoke.SEED, 1.0, wall_on=wall_on,
                             tracer=tracer, log=lambda m: None,
                             t_start=time.perf_counter())
    finally:
        tracer.close()


def test_the_smoke_cell_splits_its_rounds_with_the_wall_on():
    res = _measure(True)
    assert res["failed"] == 0 and res["rounds"] >= 1
    got = res["per_round"]
    assert set(got) == set(split.PER_ROUND)
    assert all(v > 0 for v in got.values()), got
    per = res["wall_per_round"]
    # smoke traffic: 600 images over 3 silos x 2 clients, one epoch, int8,
    # MultiKRUM; each window round merges 2 peers per silo
    from repro.configs import get_config
    from repro.core.builder import build_image_experiment
    from repro.config import FedConfig
    from repro.kernels import ops, quant
    from repro.models import build_model
    _, _, cfg, traffic, _ = smoke.parts()
    fed = FedConfig(**traffic["fed"])
    orch = build_image_experiment(get_config(cfg["registry"]), fed,
                                  seed=smoke.SEED, **traffic["builder_args"])
    clients = [c for s in orch.silos for c in s.cluster.clients]
    steps = sum(len(c.data["x"]) // c.batch_size for c in clients)
    params = build_model(get_config(cfg["registry"])).init(
        jax.random.PRNGKey(0))
    leaves = len(jax.tree_util.tree_leaves(params))
    n = fed.n_silos
    # one loss a step; a self-evaluation, an envelope (q, scales) and a
    # checkpoint of every leaf per silo; one MultiKRUM transfer
    assert got["host_syncs_per_round"] == steps + n * (3 + leaves) + 1
    n_padded = quant.padded(ops.spec_length(ops.make_flatten_spec(params)))
    payloads = (n * (n - 1) + n) * (n_padded + n_padded // quant.TILE * 4)
    x = clients[0].data["x"]
    bs = clients[0].batch_size
    batches = steps * (bs * int(np.prod(x.shape[1:])) * 4 + bs * 4 + 4)
    assert per["h2d_bytes"] == batches + payloads
    assert got["h2d_gb_per_round"] == pytest.approx(
        (batches + payloads) * 1e-9)
    # local training's host time is its three step spans and FedAvg
    train = res["harness_s_per_round"]["train"]
    parts = sum(per[f"train.{k}_s"] for k in ("feed", "dispatch", "wait",
                                               "fedavg"))
    assert 0.5 * train < parts <= train
    assert res["traced_round"]["window_s"] > 0
    assert res["traced_round"]["kernel_s"] == {}    # no device plane here


def test_the_named_kernels_ops_are_found_by_name():
    names = ["wsum_q8.3", "gram_q8", "quantize.1", "dequantize.12",
             "dequantize_batch.2", "add_q8_delta.4", "fusion.12",
             "wsum_q8_other", "copy.6"]
    assert [n for n in names if split.KERNEL_OPS.match(n)] == names[:6]


def test_with_the_wall_off_the_run_reads_nothing_of_it():
    from repro.obs import wall
    res = _measure(False)
    assert res["failed"] == 0
    assert res["per_round"] == {} and res["wall_per_round"] == {}
    assert set(res["traced_round"]["idle_by_program_span"]) <= {"other"}
    assert wall.active() is wall.NULL_WALL
