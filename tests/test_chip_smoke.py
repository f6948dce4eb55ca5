"""Rehearsal of chip_smoke.py on the CPU at a tiny size.

Each phase of the chip smoke runs here with its asserts, so a change that
would break the on-chip run fails in the tier-1 suite first. The device
check and the TPU kernel asserts belong to the chip run: `main()` refuses
the CPU, which the subprocess cases check.
"""
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402
from repro.configs import get_config, reduce_config  # noqa: E402


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


@pytest.fixture(scope="module")
def served_engine():
    task, pop, fl, auxo = cs.quickstart(rounds=20, n_clients=200, participants=40)
    return cs.phase_training("quickstart", task, pop, fl, auxo, on_tpu=False)


def test_phase_training_quickstart(served_engine):
    assert served_engine.pipeline.overlap == 1


def test_phase_training_openimage():
    # the smallest cut of this scenario found to partition
    cs.phase_training("openimage", *cs.openimage(rounds=24, n_clients=600,
                                                 participants=100),
                      on_tpu=False)


def test_phase_oracle():
    cs.phase_oracle(*cs.quickstart(rounds=20, n_clients=200, participants=40))


def test_phase_serving(served_engine):
    cs.phase_serving(served_engine, n_queries=300)


def test_phase_decode():
    cfg = reduce_config(get_config("granite-3-2b")).replace(dtype=jnp.bfloat16)
    cs.phase_decode(cfg, steps=12, page=8, on_tpu=False)


def test_phase_sharded_on_4_fake_devices():
    script = textwrap.dedent(
        """
        import chip_smoke as cs
        cs.phase_sharded(*cs.four_groups(n_clients=300, participants=60),
                         shards=4)
        print("OK")
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", script], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "OK" in out.stdout


def test_import_touches_no_device():
    script = "import chip_smoke; from jax._src import xla_bridge as xb; " \
             "assert not xb._backends, xb._backends; print('OK')"
    out = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                         capture_output=True, text=True, timeout=300,
                         env=_cpu_env())
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


@pytest.mark.parametrize("alone", [False, True], ids=["repo", "script_alone"])
def test_main_refuses_without_tpu(alone, tmp_path):
    """No TPU, or no repo around the script: non-zero exit, no result."""
    cwd, env = ROOT, _cpu_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    if alone:
        shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
        cwd = tmp_path
        env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                         capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
