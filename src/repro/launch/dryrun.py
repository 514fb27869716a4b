import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST run before any jax import: jax locks the device count on first init.
# The dry-run (and only the dry-run) builds the 512-chip production meshes,
# on fake CPU devices: it never takes an accelerator, and neither do the
# --subprocess children, which inherit this environment.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh ((16,16) 'data','model' or (2,16,16)
     'pod','data','model'),
  2. materializes ShapeDtypeStruct inputs (launch/specs.py — no allocation),
  3. jits the cell's step function with explicit in_shardings,
  4. .lower().compile() — a sharding mismatch, compile-time OOM, or
     unsupported collective here is a bug in the framework,
  5. prints compiled.memory_analysis() (fits-per-device proof) and
     cost_analysis(), parses the partitioned HLO for trip-count-adjusted
     FLOPs / HBM traffic / per-collective bytes (launch/hlostats.py),
  6. emits a JSON record consumed by benchmarks/roofline.py and
     EXPERIMENTS.md §Dry-run/§Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import pshard
from repro.config import shapes_for
from repro.configs import get_config, list_archs
from repro.core.exchange import (ExchangeConfig, make_pod_serve_step,
                                 make_train_step, make_unifyfl_round_step)
from repro.launch import hlostats
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs

# TPU v5e-class hardware constants (per chip)
PEAK_FLOPS = 197e12   # bf16
HBM_BW = 819e9        # bytes/s
LINK_BW = 50e9        # bytes/s/link ICI


def model_flops_per_device(cfg, shape, n_devices: int) -> float:
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n_active * tokens / n_devices
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_active * tokens / n_devices
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch / n_devices


def build_step(si, ex_cfg: ExchangeConfig, lr: float = 0.01):
    """Returns (fn, donate) for the cell described by input_specs output."""
    model, mesh, kind, multi_pod = si["model"], si["mesh"], si["kind"], si["multi_pod"]
    if kind == "train":
        if multi_pod:
            return make_unifyfl_round_step(model, mesh, ex_cfg, lr), (0,)
        return make_train_step(model, lr), (0,)
    if kind == "prefill":
        if multi_pod:
            return make_pod_serve_step(model, mesh, "prefill"), ()
        return (lambda params, batch: model.prefill(params, batch)), ()
    # decode
    if multi_pod:
        step = make_pod_serve_step(model, mesh, "decode")
        return (lambda params, batch, cache: step(params, batch, cache)), (2,)
    return (lambda params, batch, cache:
            model.decode_step(params, batch, cache)), (2,)


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             ex_policy: str = "top_k", compression: str = "none",
             mesh_shape=None, sharding=None, scorer: str = "loss",
             verbose: bool = True) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod, shape=mesh_shape)
    si = input_specs(arch, shape_name, multi_pod=multi_pod, mesh=mesh,
                     sharding=sharding)
    cfg, shape = si["cfg"], si["shape"]
    ex_cfg = ExchangeConfig(policy=ex_policy, compression=compression,
                            scorer=scorer)
    fn, donate = build_step(si, ex_cfg)
    kwargs = si["kwargs"]
    order = ["params", "batch", "cache"]
    args = [kwargs[k] for k in order if k in kwargs]
    in_sh = si["in_shardings"]
    with pshard.use_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    st = hlostats.analyze(txt)
    n_dev = mesh.size
    mf = model_flops_per_device(cfg, shape, n_dev)
    compute_s = st.flops / PEAK_FLOPS
    memory_s = st.traffic_bytes / HBM_BW
    coll_s = st.collective_cost_bytes / LINK_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dominant = max(terms, key=terms.get)
    rec = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": "multi_pod_2x16x16" if multi_pod else "single_pod_16x16",
        "n_devices": n_dev,
        "policy": ex_policy if (multi_pod and shape.kind == "train") else None,
        "compression": compression if multi_pod else None,
        "params_total": cfg.n_params(),
        "params_active": cfg.n_active_params(),
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", -1),
            "output_bytes": getattr(mem, "output_size_in_bytes", -1),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", -1),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", -1),
        },
        "cost_analysis": {"flops": cost.get("flops", -1.0),
                          "bytes_accessed": cost.get("bytes accessed", -1.0)},
        "hlo": st.to_dict(),
        "roofline": {
            **terms,
            "dominant": dominant,
            "model_flops_per_dev": mf,
            "useful_flops_ratio": (mf / st.flops) if st.flops > 0 else 0.0,
            "roofline_frac": (mf / PEAK_FLOPS) / max(
                compute_s, memory_s, coll_s) if max(
                compute_s, memory_s, coll_s) > 0 else 0.0,
        },
        "compile_wall_s": time.time() - t0,
    }
    if verbose:
        ma = rec["memory_analysis"]
        print(f"[{arch} x {shape_name} x {rec['mesh']}] OK "
              f"compile={rec['compile_wall_s']:.1f}s")
        print(f"  memory_analysis: args={ma['argument_bytes']/1e9:.3f}GB "
              f"out={ma['output_bytes']/1e9:.3f}GB temp={ma['temp_bytes']/1e9:.3f}GB "
              f"(per device)")
        print(f"  hlo/dev: flops={st.flops:.3e} traffic={st.traffic_bytes:.3e}B "
              f"coll={st.collective_cost_bytes:.3e}B ({st.collective_count} ops)")
        print(f"  roofline terms (s): compute={compute_s:.4f} "
              f"memory={memory_s:.4f} collective={coll_s:.4f} "
              f"-> dominant={dominant} frac={rec['roofline']['roofline_frac']:.3f}")
    return rec


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    p.add_argument("--all", action="store_true")
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--policy", default="top_k")
    p.add_argument("--compression", default="none")
    p.add_argument("--sharding", default=None,
                   help="override cfg.sharding_mode: tp | fsdp | dp")
    p.add_argument("--scorer", default="loss")
    p.add_argument("--dev", action="store_true",
                   help="reduced dev meshes (2,4)/(2,2,4) for fast iteration")
    p.add_argument("--force", action="store_true")
    p.add_argument("--subprocess", action="store_true",
                   help="run each cell in its own process (XLA CHECK-failure "
                        "crashes abort the process; this isolates them)")
    args = p.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = []
    for arch in archs:
        cfg = get_config(arch)
        shapes = [s.name for s in shapes_for(cfg)]
        if args.shape:
            shapes = [args.shape]
        for shape_name in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape_name}__{'multi' if mp else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[{tag}] cached, skipping")
                    continue
                if args.subprocess:
                    import subprocess
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape_name,
                           "--mesh", "multi" if mp else "single",
                           "--out", args.out, "--policy", args.policy,
                           "--compression", args.compression]
                    if args.dev:
                        cmd.append("--dev")
                    if args.force:
                        cmd.append("--force")
                    r = subprocess.run(cmd, capture_output=True, text=True)
                    sys.stdout.write(r.stdout)
                    if r.returncode != 0:
                        failures.append((tag, f"exit {r.returncode}"))
                        print(f"[{tag}] FAILED (subprocess exit {r.returncode})")
                        sys.stdout.write(r.stderr[-2000:])
                    continue
                try:
                    mesh_shape = ((2, 2, 4) if mp else (2, 4)) if args.dev else None
                    rec = run_cell(arch, shape_name, mp,
                                   ex_policy=args.policy,
                                   compression=args.compression,
                                   mesh_shape=mesh_shape,
                                   sharding=args.sharding,
                                   scorer=args.scorer)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"[{tag}] FAILED: {e!r}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        sys.exit(1)
    print("\nALL CELLS PASSED")


if __name__ == "__main__":
    main()
