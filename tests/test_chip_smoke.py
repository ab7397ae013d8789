"""chip_smoke.py rehearsed on the CPU: its phases at a tiny scale with the
device check injected, and its refusal to run without a TPU."""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # keep the persistent compile cache out of the test process
    import repro.compile_cache
    monkeypatch.setattr(repro.compile_cache, "use_compile_cache",
                        lambda: str(tmp_path / "cache"))
    return mod


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_one_chip_phase_at_sf_001(smoke, tmp_path, capsys):
    rc = smoke.main([], sf=0.01, require=lambda: None,
                    workdir=str(tmp_path / "work"))
    out = capsys.readouterr().out
    assert rc == 0, out
    last = _last_json(out)
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    for label in ("q6 unfused", "q6 fused", "q12"):
        assert f"{label}: cold wall" in out and f"{label}: warm wall" in out
    assert "bit-identical: True" in out     # on the CPU it is a contract
    assert not (tmp_path / "work").exists()


def test_one_chip_phase_fails_on_a_wrong_answer(smoke, tmp_path, capsys,
                                                monkeypatch):
    real = smoke.oracles
    monkeypatch.setattr(smoke, "oracles",
                        lambda sf: (real(sf)[0] * 2, real(sf)[1]))
    rc = smoke.main([], sf=0.01, require=lambda: None,
                    workdir=str(tmp_path / "work"))
    captured = capsys.readouterr()
    assert rc == 1
    assert "not within rel" in captured.err
    assert '"ok"' not in captured.out


def test_four_chip_phase_on_virtual_devices(tmp_path):
    """The --chips 4 phase on four emulated CPU devices (a child process,
    since the device count is fixed when JAX starts)."""
    code = ("import sys, chip_smoke; sys.exit(chip_smoke.main("
            "['--chips', '4'], sf=0.01, require=lambda: None, "
            f"workdir={str(tmp_path / 'work')!r}))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert _last_json(proc.stdout)["device"]["count"] == 4
    assert proc.stdout.count("decoded columns on") == 4


def test_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, SCRIPT], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "JAX found no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_refuses_to_run_outside_a_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(SCRIPT).read())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(lone)], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "run it from a checkout" in proc.stderr
    assert proc.stdout == ""
