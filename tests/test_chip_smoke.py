"""chip_smoke.py off the chip.

The script must refuse a CPU at its device phase and print no result, and
must fail without the rest of the repository. Its phases themselves run
here at the tiny size with the kernels interpreted: the tests call them
directly, past the device phase, with the compiled-kernel assertion
stubbed out (interpret mode has no ``tpu_custom_call``).
"""

from __future__ import annotations

import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _run(cwd, script, extra_env=None, args=("--tiny",)):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, str(script), *args], capture_output=True, text=True,
        env=env, cwd=cwd, timeout=300,
    )


def test_refuses_a_cpu_at_the_device_phase(tmp_path):
    r = _run(tmp_path, SMOKE)
    assert r.returncode != 0
    assert "device: no TPU" in r.stderr
    assert '"ok"' not in r.stdout and "engine:" not in r.stdout


def test_fails_without_the_repository(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run(tmp_path, tmp_path / "chip_smoke.py")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpreted(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "require_compiled", lambda fn, *args: None)
    return smoke


@pytest.mark.parametrize("phase", ["engine", "train", "serve"])
def test_phase_runs_tiny_on_cpu(interpreted, phase, capsys):
    if phase == "engine":
        interpreted.phase_engine(tiny=True, seed=0)
    elif phase == "train":
        interpreted.phase_train(tiny=True)
    else:
        interpreted.phase_serve(tiny=True)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last.startswith(f"{phase}: ok"), last


def test_four_chip_phase_runs_tiny_on_four_virtual_devices(tmp_path):
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke", {str(SMOKE)!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        smoke.phase_four_chips(tiny=True)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=tmp_path, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().splitlines()[-1].startswith("four_chips: ok")
