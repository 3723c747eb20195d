"""``chip_smoke.py`` rehearsed on the CPU: each phase at a tiny size (the
kernel in interpret mode), and the refusals that keep it off the CPU."""

import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from repro.configs import registry

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_phase(smoke):
    smoke.kernel_phase(K=1024, M=8, N=256, m=2, d=7, interpret=True)


def test_serve_phase(smoke):
    smoke.serve_phase(registry.get_smoke_config("yi-6b"), batch=2,
                      prompt_len=8, gen=3, m=2, d=7, deadline_ms=50.0)


def test_runtime_phase(smoke):
    smoke.runtime_phase(workers=3, K=64, M=8, N=64, jobs=3,
                        platform="cpu")


def _run(script: pathlib.Path, cwd: pathlib.Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "ok" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_refuses_cpu():
    """No accelerator: non-zero exit, no result line, no CPU fallback."""
    proc = _run(ROOT / "chip_smoke.py", ROOT)
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert not _printed_result(proc.stdout)


def test_refuses_without_the_repo(tmp_path):
    """The script alone, without the program beside it, fails."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert not _printed_result(proc.stdout)
