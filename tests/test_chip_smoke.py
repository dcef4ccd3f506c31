"""``chip_smoke.py`` and the compile cache its entry points turn on.

The smoke itself runs on the chip; here it must refuse the CPU, refuse to
run without the repository around it, and pass a tiny rehearsal whose
platform check the test steers to the CPU.  Each case runs in its own
process: the compile cache is process-wide JAX state.
"""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SMOKE = os.path.join(ROOT, "chip_smoke.py")

REHEARSAL = """
import sys
sys.path.insert(0, {root!r})
import chip_smoke

if __name__ == "__main__":
    chip_smoke.PLATFORM = "cpu"
    chip_smoke.SWEEP_SEEDS = 2
    chip_smoke.SWEEP_LOAD = 0.1
    chip_smoke.TRAIN_ROUNDS = 1
    raise SystemExit(chip_smoke.main([]))
"""


def _env(**extra):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def _run(args, cwd, env, timeout=600):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=timeout,
    )


def _printed_result(stdout):
    lines = stdout.strip().splitlines()
    return lines[-1] if lines and lines[-1].startswith("{") else None


def test_smoke_refuses_the_cpu(tmp_path):
    for args in ([SMOKE], [SMOKE, "--chips", "4"]):
        proc = _run(args, tmp_path, _env())
        assert proc.returncode != 0, proc.stdout
        assert _printed_result(proc.stdout) is None, proc.stdout
        assert "needs a tpu device" in proc.stderr


def test_smoke_alone_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = _env()
    env.pop("PYTHONPATH", None)
    proc = _run(["chip_smoke.py"], tmp_path, env)
    assert proc.returncode != 0, proc.stdout
    assert _printed_result(proc.stdout) is None, proc.stdout


def test_smoke_rehearsal_on_cpu_uses_the_given_cache_dir(tmp_path):
    script = tmp_path / "rehearse.py"
    script.write_text(REHEARSAL.format(root=ROOT))
    cache = tmp_path / "jax_cache"
    proc = _run([str(script)], tmp_path, _env(JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr[-3000:]}"
    assert json.loads(_printed_result(proc.stdout)) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert f"compile cache: {cache}" in proc.stdout
    assert any(cache.iterdir()), "nothing was written to the compile cache"
    assert "FAIL" not in proc.stdout


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    probe = (
        "import jax\n"
        "from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache\n"
        "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir,"
        " CHECKOUT_CACHE_DIR)\n"
    )
    env = _env(PYTHONPATH=os.path.join(ROOT, "src"))
    proc = _run(["-c", probe], tmp_path, env)
    assert proc.returncode == 0, proc.stderr
    got, configured, checkout = proc.stdout.split()
    assert got == configured == checkout
    assert checkout == os.path.join(ROOT, "artifacts", "jax_cache")

    given = str(tmp_path / "elsewhere")
    proc = _run(["-c", probe], tmp_path, dict(env, JAX_COMPILATION_CACHE_DIR=given))
    assert proc.returncode == 0, proc.stderr
    got, configured, _ = proc.stdout.split()
    assert got == configured == given
