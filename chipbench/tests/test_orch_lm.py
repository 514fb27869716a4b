"""The orch_lm driver's window and ``correct`` path on a smoke-size qwen3 on
the CPU (the cell's driver, traffic, reference and committed limits, with
widths, sequences and steps cut down), its refusal of a program that cannot
bound its store, and the counts it hands the yardstick."""
import time
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import dequant_cost, harness, q8ref
from chipbench.tests import smoke

CELL = "qwen3-1.7b-4l.sync-int8-loss"
SEED = 2 ** 31 + 23
SMOKE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=128, vocab_size=256)
DRIVER = harness.load_module("drivers", "orch_lm")
CFG_MOD = harness.load_module("configs", "qwen3-1.7b-4l")
# the number each planted fault must push past its limit
MOVES = {"frozen_step": "update_gap", "half_batch": "update_gap",
         "no_exchange": "merge_gap", "altered_answer": "loss_score_gap",
         "altered_payload": "peer_mismatch", "no_qk_norm": "grad_gap"}


def parts():
    man = harness.manifest()
    cell = harness.find_cell(man, CELL)
    cfg = harness.load_json("configs", cell["config"])
    cfg["overrides"] = dict(SMOKE)
    cfg["model"] = {k: v for k, v in cfg["model"].items() if k not in SMOKE
                    and k not in ("d_model", "d_ff")}
    traffic = harness.load_json("traffic", cell["traffic"])
    traffic["builder_args"].update(seq_len=32, batch_size=2,
                                   steps_per_epoch=2, stream_len=3000)
    limits = harness.load_json("checks", CELL)["limits"]
    return man, cell, cfg, traffic, limits


def drive(fault=None, seconds=0.5):
    _, _, cfg, traffic, limits = parts()
    out = DRIVER.drive(cfg, traffic, SEED, seconds, traced=False,
                       compiles=harness.CompileCounter(), log=lambda m: None,
                       t_start=time.perf_counter(), fault=fault)
    return out, limits


def run_cell(traced: bool, seconds=1.0):
    man, cell, cfg, traffic, limits = parts()
    args = types.SimpleNamespace(seed=SEED, seconds=seconds, trace=int(traced))
    return smoke.run_module().run_cell(jax, man, cell, cfg, traffic, limits,
                                       harness.peaks("TPU v5 lite"), args)


def test_a_sound_run_is_correct_and_reports_its_end_to_end_metrics():
    res = run_cell(traced=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 3
    assert set(res["metrics"]) == {"round_s", "setup_s"}
    assert set(res["checks"]) == set(MOVES.values()) | {
        "loss_gap", "encode_mismatch", "missing"}


def test_a_traced_run_reports_what_the_store_holds():
    """The wall is on in traced runs: the store's counters give what its
    nodes hold. The CPU has no device plane, so the kernel's roofline finds
    nothing to read and is left out, never reported as 0."""
    res = run_cell(traced=True)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    assert got["store_gb_held"]["value"] > 0
    assert got["store_gb_held"]["unit"] == "GB"
    assert "dequantize_batch_roofline" not in got
    from repro.obs import wall
    wall.switch(False)


@pytest.mark.parametrize("fault", DRIVER.FAULTS)
def test_a_planted_fault_pushes_its_number_past_its_limit(fault):
    out, limits = drive(fault)
    numbers = DRIVER.compare(out, CFG_MOD, SEED)
    ok, checks = harness.decide(numbers, limits)
    assert not ok and numbers[MOVES[fault]] > limits[MOVES[fault]], checks


def test_the_control_fails_a_number():
    """The reference with float8_e4m3fn matmul inputs, the precision below
    the configuration's bfloat16, put in the program's place."""
    out, limits = drive()
    numbers = dict(DRIVER.control(out, CFG_MOD, SEED), missing=0.0)
    ok, checks = harness.decide(numbers, limits)
    assert not ok, checks


def test_it_refuses_a_program_without_the_retention_counters(monkeypatch):
    from repro.obs import metrics
    schema = {k: v for k, v in metrics.SCHEMAS["wall"].items()
              if k != "store_bytes_dropped"}
    monkeypatch.setitem(metrics.SCHEMAS, "wall", schema)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit, match="store_bytes_dropped"):
        drive()
    assert time.perf_counter() - t0 < 5


def test_forward_flops_match_xlas_count_of_the_reference():
    """XLA's count of the reference's logits (its matmuls, plus elementwise
    work we leave out) at smoke widths, against the count from shapes."""
    from repro.config import replace
    from repro.configs.qwen3_1_7b import smoke_config
    from repro.models import build_model, qwen3_ref

    # wide enough that the matmuls dominate XLA's count, as at full width
    cfg = replace(smoke_config(), n_layers=1, d_model=512, d_ff=2048,
                  head_dim=64, n_heads=8, n_kv_heads=4, vocab_size=4096)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    S = 16
    toks = jnp.zeros((1, S), jnp.int32)
    d = qwen3_ref.Dims.of(cfg)
    fn = jax.jit(lambda p, t: qwen3_ref.logits(p, t, d))
    cost = fn.lower(params, toks).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    c = {"hidden_size": cfg.d_model, "head_dim": cfg.head_dim,
         "num_attention_heads": cfg.n_heads,
         "num_key_value_heads": cfg.n_kv_heads,
         "intermediate_size": cfg.d_ff, "num_hidden_layers": cfg.n_layers,
         "vocab_size": cfg.vocab_size}
    ours = S * CFG_MOD.fwd_flops_per_token(S, c)
    # the reference computes the whole S x S score matrix; the count takes
    # the causal half of it
    full = ours + cfg.n_layers * 2 * 2 * cfg.n_heads * cfg.head_dim * S * (
        S - 1) / 2
    assert full <= cost["flops"] <= 1.1 * full


def test_round_flops_are_three_forwards_per_trained_token():
    f = CFG_MOD.fwd_flops_per_token(512)
    work = {"train_tokens": 10, "eval_tokens": 4, "seq_len": 512}
    assert CFG_MOD.round_flops(work) == 30 * f + 4 * f


def test_the_cells_work_is_counted_from_its_shapes():
    out, _ = drive()
    # 3 silos x 1 client x 2 steps of 2 x 32 tokens; 4 windows of 32 tokens
    # per silo, each evaluated by itself and by its 2 scorers
    assert out["work"] == {"train_tokens": 3 * 2 * 2 * 32, "seq_len": 32,
                           "eval_tokens": 3 * 4 * 32 * 3}


def test_dequantize_batch_bytes_and_ops_count_the_algorithms_data():
    n = 515_918_848
    b, ops = dequant_cost.dequantize_batch_cost(2, n, 2)
    assert b == 2 * n + 2 * -(-n // q8ref.TILE) * 4 + 2 * n * 2
    assert ops == 2 * n
