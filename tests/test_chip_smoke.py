"""chip_smoke.py and the compile-cache helper, as far as a machine without
a chip can check them: the smoke refuses to run without a TPU, its CPU
rehearsal walks every phase and marks every line, and the helper leaves
the cache where JAX_COMPILATION_CACHE_DIR puts it."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(args, tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # one device, like the one-chip machine the driver checks on
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return subprocess.run(
        [sys.executable, SMOKE, *args], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=300,
    )


def test_smoke_fails_fast_without_a_chip(tmp_path):
    res = _run_smoke([], tmp_path)
    assert res.returncode != 0
    assert "JAX found platform 'cpu'" in res.stderr
    assert "phase 1" not in res.stdout
    assert '"ok"' not in res.stdout  # no result line


def test_smoke_cpu_rehearsal_passes_and_marks_every_line(tmp_path):
    res = _run_smoke(["--cpu-rehearsal"], tmp_path)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    lines = res.stdout.splitlines()
    assert lines and all(ln.startswith("[cpu-rehearsal] ") for ln in lines)
    assert lines[-1].endswith(
        '{"ok": true, "device": {"platform": "cpu", "kind": "cpu", '
        '"count": 1}}'
    )
    for phase in ("phase 0", "phase 1", "phase 2", "phase 3"):
        assert any(phase in ln for ln in lines), phase
    assert any("olap.spillover.spilled moved" in ln for ln in lines)


def test_compile_cache_is_placed_from_outside(monkeypatch):
    import jax

    from janusgraph_tpu.olap import device

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.append((k, v))
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/elsewhere")
    device.configure_compile_cache()
    assert updates == []  # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    device.configure_compile_cache()
    assert updates == [
        ("jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache"))
    ]
