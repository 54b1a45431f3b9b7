"""End-to-end behaviour tests for the system."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_quickstart_example_runs():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "examples", "quickstart.py")],
        capture_output=True, text=True, env=env, timeout=590, cwd=_ROOT,
    )
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    assert "C == A@B: True" in res.stdout
    assert "cannon-like: True" in res.stdout


def test_train_example_loss_decreases():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "examples", "train_lm.py"),
         "--preset", "demo", "--steps", "40", "--batch", "4", "--seq", "128"],
        capture_output=True, text=True, env=env, timeout=590, cwd=_ROOT,
    )
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    # parse "first logged loss X -> last Y  (restarts: N)"
    line = [l for l in res.stdout.splitlines() if "first logged loss" in l][0]
    first = float(line.split("loss")[1].split("->")[0])
    last = float(line.split("-> last")[1].split("(")[0])
    assert last < first


def test_serve_example_runs():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "examples", "serve_batched.py"),
         "--max-new", "6", "--batch", "2"],
        capture_output=True, text=True, env=env, timeout=590, cwd=_ROOT,
    )
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    # plan-routed serving: warm bucket hit + throughput line
    assert "tok/s" in res.stdout
    assert "bucket=4x16" in res.stdout
    assert "hit rate 1.0" in res.stdout


def test_dryrun_entry_single_cell():
    """The multi-pod dry-run machinery end-to-end for one (arch, shape) on
    both meshes (the full 33x2-cell sweep is run separately; this keeps the
    harness honest in CI)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "xlstm-350m", "--shape", "decode_32k",
         "--out", "/tmp/dryrun_ci.json"],
        capture_output=True, text=True, env=env, timeout=590, cwd=_ROOT,
    )
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    assert "2/2 cells compiled" in res.stdout, res.stdout[-2000:]


def test_elastic_remesh_state_roundtrip():
    """Simulated pod loss: state built for a (2, 2) mesh re-placed onto the
    survivor mesh; values preserved."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.mesh import make_mesh
from repro.runtime.elastic import shrink_after_failure, replace_state
state = {
    "step": jnp.int32(7),
    "master": {"wq": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)},
    "m": {"wq": jnp.ones((8, 8), jnp.float32)},
    "v": {"wq": jnp.ones((8, 8), jnp.float32)},
}
mesh2 = make_mesh((2, 2, 2), ("pod", "data", "model"))
st2 = replace_state(state, mesh2)
surv = shrink_after_failure(mesh2, lost_pod=1)
assert "pod" not in surv.axis_names and surv.devices.size == 4
st1 = replace_state(st2, surv)
np.testing.assert_array_equal(np.asarray(st1["master"]["wq"]),
                              np.asarray(state["master"]["wq"]))
print("ELASTIC_OK")
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=590)
    assert "ELASTIC_OK" in res.stdout, res.stdout + res.stderr


def test_chip_smoke_refuses_cpu():
    """Without a TPU the bring-up script fails and prints no result."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300, cwd=_ROOT,
    )
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_chip_smoke_admitted_plans():
    """The four-chip phase's admission table matches what the planner
    admits, so the phase fails only on a refusal nobody expected."""
    script = r"""
import chip_smoke, jax
plans = chip_smoke.admitted_plans(jax.devices(), 4096, 3840, 10240)
print("ADMITTED", len(plans))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _ROOT
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=300, cwd=_ROOT)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    assert "ADMITTED 60" in res.stdout


def test_serve_launcher_writes_compile_cache(tmp_path):
    """The serving launcher on the CPU, with its compile cache placed from
    outside: the cache lands in JAX_COMPILATION_CACHE_DIR."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    # CPU compiles of a smoke config take well under JAX's 1 s default
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--smoke",
         "--arch", "h2o-danube-3-4b", "--buckets", "4x16", "--batch", "3",
         "--max-new", "4", "--max-seq", "64"],
        capture_output=True, text=True, env=env, timeout=300, cwd=_ROOT,
    )
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    assert "bucket=4x16" in res.stdout
    assert "12 tokens" in res.stdout
    assert any((tmp_path / "cache").iterdir())


def test_enable_compile_cache_placement(monkeypatch):
    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
