"""How the program meets the device: the persistent compile cache, data-plane
children held to the CPU, the chip smoke test refusing anything but a TPU,
and the docs' links resolving."""
import os
import shutil
import subprocess
import sys
import textwrap

import jax

from repro import utils

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CACHED_RUN = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, "src")
    from repro.utils import enable_compile_cache
    where = enable_compile_cache()
    import jax, jax.numpy as jnp
    hits = []
    jax.monitoring.register_event_listener(
        lambda e, **_: hits.append(e) if e.endswith("/cache_hits") else None)
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
    print(json.dumps({"dir": where, "hits": len(hits)}))
""")


def _cached_run(env: dict) -> dict:
    import json
    out = subprocess.run([sys.executable, "-c", _CACHED_RUN], cwd=ROOT,
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_lands_in_env_dir_and_hits_on_rerun(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    first = _cached_run(env)
    assert first["dir"] == str(tmp_path) and first["hits"] == 0
    assert os.listdir(tmp_path), "nothing cached"
    second = _cached_run(env)
    assert second["hits"] >= 1, second


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        got = utils.enable_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])


def test_cpu_only_children_sets_and_restores_env(monkeypatch):
    probe = [sys.executable, "-c",
             "import os; print(os.environ.get('JAX_PLATFORMS'))"]
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with utils.cpu_only_children():
        child = subprocess.run(probe, capture_output=True, text=True)
    assert child.stdout.strip() == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "tpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with utils.cpu_only_children():
        pass
    assert "JAX_PLATFORMS" not in os.environ


def test_chip_smoke_refuses_the_cpu():
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU found" in out.stdout


def test_chip_smoke_refuses_to_run_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode != 0 and '"ok": true' not in out.stdout


def test_docs_links_resolve():
    """Every doc the README and the docs name exists, every doc is reachable
    from the README, and the analyzer's rule catalog matches its registry."""
    from tools import check_docs_links

    assert check_docs_links.main() == 0
