"""Entry points: the compile cache's placement, the chip smoke's refusal
without a TPU, full-width serving's refusals, and the packed-at-load
weight build that full-width serving uses."""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import compile_cache
from repro.models import model_zoo
from repro.runtime import serve_loop

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def _python(code: str, **env_extra) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(PYTHONPATH=SRC, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)


def test_compile_cache_lands_where_the_env_var_says(tmp_path):
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        # cache even this tiny compile (JAX skips compiles under 1 s)
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")
    r = _python(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(tmp_path), str(tmp_path)]
    assert os.listdir(tmp_path), "no cache entry was written"


def test_compile_cache_default_is_a_fixed_ignored_checkout_path():
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    r = _python(code)
    assert r.returncode == 0, r.stderr[-2000:]
    want = os.path.join(ROOT, ".jax_cache")
    assert r.stdout.split() == [want, want]
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_smoke_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


@pytest.mark.parametrize("flag", ["--compare-percall", "--quant=int8"])
def test_serve_full_width_refuses_a_second_weight_copy(monkeypatch, flag):
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", ["serve", "--arch", "stablelm-3b",
                                      "--full-width", flag])
    with pytest.raises(SystemExit) as e:
        serve.main()
    assert e.value.code == 2


@pytest.mark.parametrize("fuse", [True, False])
def test_build_packed_matches_pack_of_build(fuse):
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    # the same init+pack as ONE compiled program (eager init rounds
    # differently from compiled init in the last bit)
    want = jax.jit(lambda: model_zoo.pack_for_inference(
        cfg, model_zoo.build(cfg, 3), fuse=fuse))()
    got = model_zoo.build_packed(cfg, 3, fuse=fuse)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_build_packed_resolves_pack_blocks_under_its_plan_store(tmp_path):
    from repro import gemm
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    store = gemm.PlanStore(tmp_path / "plans.json")
    gemm.plan_cache_clear()
    try:
        model_zoo.build_packed(cfg, plan_store=store)
        cold = store.info()
        assert cold.misses > 0 and cold.entries == cold.misses
        gemm.plan_cache_clear()
        model_zoo.build_packed(cfg, plan_store=store)
        warm = store.info()
        assert warm.hits == cold.misses and warm.misses == cold.misses
    finally:
        gemm.plan_cache_clear()


def test_engine_serves_prepacked_params():
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    prompts = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 10)),
        jnp.int32)
    raw = jax.jit(lambda: model_zoo.build(cfg))()   # build_packed's init
    out_raw, _ = serve_loop.Engine(cfg, raw, max_len=32,
                                   packed=True).generate(prompts, 4)
    eng = serve_loop.Engine(cfg, model_zoo.build_packed(cfg), max_len=32,
                            packed=True)
    out_pre, _ = eng.generate(prompts, 4)
    np.testing.assert_array_equal(np.asarray(out_pre), np.asarray(out_raw))
    with pytest.raises(ValueError, match="pack-time"):
        serve_loop.Engine(cfg, eng.params, packed=True, quant="int8")


def test_serve_profile_dir_puts_the_schedulers_spans_in_the_profile(
        monkeypatch, tmp_path, capsys):
    import glob
    from repro import obs
    from repro.launch import serve
    # the persistent cache would outlive this test in the worker process
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "stablelm-3b", "--batch", "1", "--prompt-len",
        "8", "--max-new", "2", "--max-len", "32", "--requests", "2",
        "--batch-slots", "2", "--prefill-chunk", "8",
        "--profile-dir", str(tmp_path)])
    serve.main()
    assert obs.active_tracer() is None             # uninstalled at exit
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in pd.planes if plane.name.startswith("/host")
             for line in plane.lines for e in line.events
             if "span_id" in dict(e.stats)}
    assert {"sched_step", "prefill_chunk", "decode_tick", "fence"} <= names
    assert "profile written" in capsys.readouterr().out


def test_serve_refuses_two_span_sinks(monkeypatch, tmp_path):
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "stablelm-3b", "--profile-dir", str(tmp_path),
        "--trace-out", str(tmp_path / "t.json")])
    with pytest.raises(SystemExit) as e:
        serve.main()
    assert e.value.code == 2
