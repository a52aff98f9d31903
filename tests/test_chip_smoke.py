"""The bring-up contract of the entry points (ISSUE 22): chip_smoke.py runs
its whole control flow at toy size on a CPU that was asked for, refuses a
CPU that was not, and fails when a phase does; the compile cache is placed
from outside or at one fixed in-checkout path.

Every child gets JAX_COMPILATION_CACHE_DIR pointed at tmp_path: the suite
must not write CPU cache entries into the checkout, which is copied to the
chip as it stands."""

import json
import os
import subprocess
import sys

from akka_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, timeout, **env):
    full_env = dict(os.environ, JAX_PLATFORMS="cpu",
                    JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"), **env)
    return subprocess.run([sys.executable, *args], env=full_env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_tiny_passes_on_requested_cpu(tmp_path):
    r = _run([SMOKE, "--tiny"], tmp_path, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": last["device"]["count"]}}
    phases = [json.loads(ln)["phase"] for ln in r.stdout.splitlines()
              if ln.startswith('{"phase"')]
    assert phases == ["a", "b", "c", "r", "s", "d", "a", "served"]


def test_chip_smoke_refuses_a_cpu_without_tiny(tmp_path):
    # the contract's default mode wants a TPU: on a CPU the first worker
    # fails before any phase, so the parent does, and no result is printed
    r = _run([SMOKE], tmp_path, timeout=300)
    assert r.returncode != 0 and '{"ok"' not in r.stdout, r.stdout
    assert "not 'tpu'" in r.stderr


def test_chip_smoke_worker_fails_with_its_phase(tmp_path):
    boom = ("import sys, chip_smoke\n"
            "def boom(w): raise RuntimeError('phase made to raise')\n"
            "chip_smoke.PHASES['a'] = boom\n"
            "sys.exit(chip_smoke.main(sys.argv[1:]))\n")
    out = tmp_path / "w" / "result.json"
    out.parent.mkdir()
    r = _run(["-c", boom, "--worker", "a", "--tiny", "--out", str(out)],
             tmp_path, timeout=300)
    assert r.returncode != 0 and "phase made to raise" in r.stderr
    assert not out.exists()


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # JAX read the variable itself; code sets no other
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path(tmp_path):
    # one path inside the checkout, whatever the working directory (the
    # path is part of every cache key), and git ignores it
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    code = ("from akka_tpu.utils.compile_cache import compile_cache_dir; "
            "print(compile_cache_dir())")
    seen = {subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                           capture_output=True, text=True, check=True,
                           timeout=120).stdout.strip()
            for cwd in (str(tmp_path), REPO)}
    assert seen == {os.path.join(REPO, ".jax_cache")}
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().splitlines()
