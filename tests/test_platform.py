"""The device is JAX's own choice: chip_smoke.py refuses a host that has no
chip, and the compile cache goes where JAX_COMPILATION_CACHE_DIR says (or
to one fixed directory inside the checkout)."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_a_chip(tmp_path, where):
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    proc = subprocess.run(
        [sys.executable, str(script)],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert proc.stderr.strip().splitlines()[-1] == (
        "chip_smoke: FAIL: found platform 'cpu', need 'tpu'"
    )


def _cache_dir(env) -> str:
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax, kafka_topic_analyzer_tpu.jax_support; "
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.strip()


@pytest.mark.parametrize("set_by_env", [True, False])
def test_compile_cache_dir(tmp_path, set_by_env):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if set_by_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        assert _cache_dir(env) == str(tmp_path)
    else:
        assert _cache_dir(env) == os.path.join(REPO, ".jax_cache")
