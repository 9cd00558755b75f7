"""chip_smoke.py and the compile-cache placement, as far as a CPU can
check them: the smoke refuses anything but a TPU, fails when a phase
fails, and never touches jax in its parent; the cache directory is the
environment's or one fixed path inside the checkout."""

import json
import os
import subprocess
import sys
import time

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

_CACHE_PROBE = (
    "import json, jax\n"
    "from code2vec_tpu.utils import device\n"
    "before = device.compile_cache_dir()\n"
    "used = device.configure_compile_cache()\n"
    "print(json.dumps({'dir': before, 'used': used, "
    "'jax': jax.config.jax_compilation_cache_dir}))\n")


def _cache_probe(tmp_path, **env_overrides):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO_ROOT
    env.update(env_overrides)
    # cwd outside the checkout: the fixed path must not depend on it
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=120, check=True).stdout
    return json.loads(out.splitlines()[-1])


def test_cache_dir_from_environment_is_left_alone(tmp_path):
    placed = str(tmp_path / "placed-from-outside")
    got = _cache_probe(tmp_path, JAX_COMPILATION_CACHE_DIR=placed)
    # the function reports it, JAX read it by itself, nothing replaced it
    assert got == {"dir": placed, "used": placed, "jax": placed}


def test_cache_dir_default_is_one_fixed_path_in_the_checkout(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    first = _cache_probe(tmp_path / "a", JAX_PLATFORMS="")
    second = _cache_probe(tmp_path / "b", JAX_PLATFORMS="cpu")
    fixed = os.path.join(REPO_ROOT, ".jax_cache")
    assert first["dir"] == second["dir"] == fixed
    # unpinned, the fixed directory is what JAX is given ...
    assert first["used"] == first["jax"] == fixed
    # ... and a process pinned to the CPU keeps JAX's default (none)
    assert second["used"] is None and second["jax"] is None
    with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
        ignored = f.read().split()
    assert {".jax_cache/", ".chip_smoke/", "chiprun_out/"} <= set(ignored)


def test_importing_the_smoke_imports_no_jax():
    code = ("import sys, chip_smoke\n"
            "bad = [m for m in ('jax', 'jaxlib', 'code2vec_tpu', 'numpy') "
            "if m in sys.modules]\n"
            "assert not bad, bad\n")
    subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT, check=True,
                   timeout=60)


def test_smoke_refuses_the_cpu_before_generating_data():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO_ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert time.monotonic() - t0 < 60
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert "build:" not in proc.stdout and "generate:" not in proc.stdout
    assert '"ok"' not in proc.stdout


def test_bench_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "bench.py"], cwd=REPO_ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


@pytest.fixture
def smoke_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(chip_smoke, "LOGS", str(tmp_path / "work" / "logs"))
    return chip_smoke


def test_phase_runner_fails_on_nonzero_exit_and_on_timeout(smoke_in_tmp):
    text, _ = smoke_in_tmp.run_phase(
        "ok", [sys.executable, "-c", "print('fine')"], 60.0)
    assert "fine" in text
    with pytest.raises(chip_smoke.PhaseFailed) as e:
        smoke_in_tmp.run_phase(
            "stub", [sys.executable, "-c",
                     "import sys; print('boom'); sys.exit(3)"], 60.0)
    assert "exit code 3" in str(e.value) and "boom" in str(e.value)
    t0 = time.monotonic()
    with pytest.raises(chip_smoke.PhaseFailed, match="timed out"):
        smoke_in_tmp.run_phase(
            "hang", [sys.executable, "-c", "import time; time.sleep(600)"],
            1.0)
    assert time.monotonic() - t0 < 30


def test_a_failed_phase_fails_the_smoke(smoke_in_tmp, monkeypatch, capsys):
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "probe_device", lambda: tpu)
    monkeypatch.setattr(
        chip_smoke, "build_native",
        lambda: chip_smoke.run_phase(
            "build", [sys.executable, "-c", "raise SystemExit(7)"], 60.0))
    assert chip_smoke.main() == 1
    out, err = capsys.readouterr()
    assert "build: exit code 7" in err
    assert '"ok"' not in out
