"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its phases
pass at a small size (kernels in interpret mode, smoke-sized configs).

The pod phase needs several devices, so it runs in a subprocess whose
XLA_FLAGS give the CPU backend four (jax fixes the device count when it
initializes)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, SMOKE], env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(ROOT)


def test_cnn_phase_passes_on_cpu(chip_smoke, capsys):
    chip_smoke.cnn_phase(n_train=300, n_test=120)
    out = capsys.readouterr().out
    assert "fused q8 kernels match their oracles on 3 payloads" in out
    assert "cnn round 2:" in out


def test_lm_phase_passes_on_cpu(chip_smoke, capsys):
    from repro.configs import get_smoke_config
    chip_smoke.lm_phase(get_smoke_config("qwen3-1.7b"), steps_per_epoch=2)
    out = capsys.readouterr().out
    assert "add_q8_delta matches its oracle on 3 delta payloads" in out
    assert "round 2: all 3 silos submitted" in out


POD = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {root!r})
    import chip_smoke
    from repro.configs import get_smoke_config
    chip_smoke.pod_phase(get_smoke_config("qwen3-1.7b"), batch=4, seq=32)
    print("POD_OK")
""")


def test_pod_phase_on_4_virtual_devices():
    env = _env(PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", POD.format(root=ROOT)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "POD_OK" in r.stdout
    assert "silo i's block sits on device i only" in r.stdout
    assert "policy 'all' equals the mean of 4 single-device steps" in r.stdout
