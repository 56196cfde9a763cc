"""Integration tests: kernels-in-model parity, split serving vs full
forward, end-to-end training loss decrease, serve driver, dry-run
machinery on a CI-scale mesh."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import transformer as tfm
from repro.runtime.splitpoint import SplitRunner

B, S = 2, 64


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "rwkv6-3b",
                                  "recurrentgemma-2b"])
def test_pallas_model_parity(arch):
    """Forward with use_pallas_kernels (interpret) == jnp path."""
    cfg = reduced(get_config(arch))
    cfg_k = dataclasses.replace(cfg, use_pallas_kernels=True)
    params = tfm.init_model(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    h1, _, _ = tfm.forward(params, cfg, None, tokens=toks, positions=pos,
                           mode="train")
    h2, _, _ = tfm.forward(params, cfg_k, None, tokens=toks, positions=pos,
                           mode="train")
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=5e-4, rtol=1e-2)


def test_split_serving_matches_full_forward():
    cfg = reduced(get_config("deepseek-7b"))
    params = tfm.init_model(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 16), 0,
                              cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(16), (B, 16))
    hidden, _, _ = tfm.forward(params, cfg, None, tokens=toks, positions=pos,
                               mode="train")
    ref = tfm.logits_fn(params, hidden, cfg, None)
    runner = SplitRunner(cfg, params, B, 16)
    for l in [0, 1, cfg.n_layers // 2, cfg.n_layers]:
        logits, bb = runner.run(l, tokens=toks)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   atol=1e-4, rtol=1e-3)
        assert bb == B * 16 * cfg.d_model * 4   # f32 boundary payload


def test_training_reduces_loss_end_to_end(tmp_path):
    from repro.launch import train as train_mod
    losses = train_mod.main([
        "--arch", "qwen2-1.5b", "--reduced", "--steps", "40",
        "--batch", "8", "--seq", "32", "--lr", "3e-3",
        "--ckpt", str(tmp_path / "ckpt")])
    assert losses[-1] < losses[0] - 0.1, (losses[0], losses[-1])


def test_grad_compression_training_still_converges(tmp_path):
    from repro.launch import train as train_mod
    losses = train_mod.main([
        "--arch", "qwen2-1.5b", "--reduced", "--steps", "30",
        "--batch", "8", "--seq", "32", "--lr", "3e-3",
        "--compress-grads", "--ckpt", str(tmp_path / "ckpt")])
    assert losses[-1] < losses[0] - 0.05


def test_serve_driver_places_split(monkeypatch, tmp_path):
    from repro.launch import serve as serve_mod
    # with the variable set, the driver leaves the process's compile-cache
    # settings alone (the other tests of this worker keep theirs)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    res = serve_mod.main(["--arch", "recurrentgemma-2b", "--reduced",
                          "--budget", "10"])
    assert res.n_evals <= 10
    assert jax.config.jax_compilation_cache_dir == before


def test_benchmark_runner_exits_nonzero_on_failure(monkeypatch, capsys):
    """A benchmark that raises is reported and counted in the exit code;
    the others still run."""
    from benchmarks import run as bench_run

    def boom():
        raise RuntimeError("broken benchmark")

    monkeypatch.setattr(bench_run, "BENCHES", [
        ("ok", lambda: 3, lambda o: f"{o} rows"),
        ("boom", boom, lambda o: ""),
    ])
    monkeypatch.setattr(sys, "argv", ["run"])
    assert bench_run.main() == 1
    out = capsys.readouterr()
    assert "CSV,ok," in out.out and "3 rows" in out.out
    assert "ERROR RuntimeError: broken benchmark" in out.out
    assert "failed: boom" in out.err


def test_dryrun_cell_on_ci_mesh():
    """The dry-run machinery end-to-end on an 8-device CI mesh."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               REPRO_TEST_MESH="2x4",
               PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c",
         "from repro.launch.dryrun import run_cell; "
         "r = run_cell('qwen2-1.5b', 'decode_32k', 'pod'); "
         "assert r['status'] == 'ok', r; "
         "assert r['analysis'] and 'flops' in r['analysis'], r['analysis']; "
         "print('ci-dryrun ok', r['hlo_gflops'])"],
        capture_output=True, text=True, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), timeout=420)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ci-dryrun ok" in r.stdout
