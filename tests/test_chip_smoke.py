"""``chip_smoke.py`` on the CPU -- its phases at a tiny sensor size, and
its refusal to report a result when JAX finds no TPU -- plus the
plumbing around a chip run: the compile cache's location and the
benchmark runner's refusal to start a child while it holds a chip."""
import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro.configs.rdf_sensor import SMALL

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
TINY = dataclasses.replace(SMALL, n_observations=1_000)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_at_tiny_size(smoke):
    store = smoke.make_store(TINY)
    ref, rep = smoke.host_reference(store)
    assert rep.n_triples_after < rep.n_triples_before
    svc, st = smoke.device_compaction(store, ref)
    assert st["lowerings"] > 0 and st["buckets"]
    sv = smoke.serve(svc, store, wave_size=8, warm_waves=1, n_bgp=2)
    assert sv["requests"] == 18 and sv["match_lowerings"] > 0
    ing = smoke.ingest(svc, store, n_batches=3)
    assert ing["redetections"] >= 1 and ing["redetect_lowerings"] > 0


def test_phase_check_catches_a_wrong_compaction(smoke):
    store = smoke.make_store(TINY)
    ref, _ = smoke.host_reference(store)
    from repro.api import Compactor
    other = Compactor(detector="gfsp", backend="host",
                      min_predicted_savings=10**9)
    other.run(store)
    with pytest.raises(smoke.SmokeFailure, match="factorization differs"):
        smoke.check_same_compaction(other.snapshot, ref, "probe")


def test_four_chip_phase_on_virtual_devices():
    """The ``--chips 4`` path over four forced host devices: sharded
    digest == host digest and an all-to-all in the compiled sweep."""
    code = textwrap.dedent(f"""
        import dataclasses, sys
        sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, "src")!r}]
        import chip_smoke
        from repro.configs.rdf_sensor import SMALL
        out = chip_smoke.run_four_chips(
            dataclasses.replace(SMALL, n_observations=1_000))
        print(out["sharded"]["lowerings"])
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert int(r.stdout.strip().splitlines()[-1]) > 0


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert smoke.main(["--chips", "4"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_script_alone_prints_no_result(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode != 0
    for line in r.stdout.splitlines():
        try:
            assert not json.loads(line).get("ok")
        except (ValueError, AttributeError):
            pass


def test_compile_cache_defers_to_the_environment(monkeypatch):
    import jax

    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax

    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.enable_compile_cache() == got   # fixed path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_bench_runner_refuses_a_child_while_holding_a_chip(monkeypatch):
    import jax
    from jax._src import xla_bridge
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    jax.devices()                          # a CPU backend: children ok
    run._check_chip_free()
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the tpu backend"):
        run._check_chip_free()
    with pytest.raises(RuntimeError, match="holds the tpu backend"):
        run._run_scale_cell("sensor", 10, "plain")
