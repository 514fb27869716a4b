"""The cell paper-cnn.sync-int8-multikrum at a size a CPU test holds: the
same configuration, driver, traffic parameters and committed limits, with
fewer images and one local epoch."""
import importlib.util
import time
import types

import jax

from chipbench import harness

CELL = "paper-cnn.sync-int8-multikrum"
SEED = 2 ** 31 + 11          # wider than 32 signed bits, as a check's seeds


def parts():
    man = harness.manifest()
    cell = harness.find_cell(man, CELL)
    cfg = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    traffic["builder_args"].update(n_train=600, n_test=200)
    traffic["fed"]["local_epochs"] = 1
    limits = harness.load_json("checks", CELL)["limits"]
    return man, cell, cfg, traffic, limits


def run_module():
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", harness.HERE / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def drive(fault=None, seconds=1.0):
    """One driven run and its numbers, with ``fault`` planted."""
    _, cell, cfg, traffic, _ = parts()
    driver = harness.load_module("drivers", traffic["driver"])
    cfg_mod = harness.load_module("configs", cell["config"])
    out = driver.drive(cfg, traffic, SEED, seconds, traced=False,
                       compiles=harness.CompileCounter(), log=lambda m: None,
                       t_start=time.perf_counter(), fault=fault)
    return driver, cfg_mod, out


def run_cell(traced: bool, seconds=1.0):
    man, cell, cfg, traffic, limits = parts()
    args = types.SimpleNamespace(seed=SEED, seconds=seconds, trace=int(traced))
    return run_module().run_cell(jax, man, cell, cfg, traffic, limits,
                                 harness.peaks("TPU v5 lite"), args)
