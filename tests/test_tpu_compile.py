"""Compile the planner's main programs for a described TPU v5e chip.

No chip is attached: the TPU compiler builds each program for a device
described by ``topologies.get_topology_desc`` and refuses what the chip
would refuse (block shapes off the (8, 128) tiling, more fast memory than
a kernel may use, a program that does not fit). Nothing runs, so these
tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and pytest
workers import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import WholeRunBayesSplitEdge
from repro.core.engine_config import EngineConfig
from repro.core import wholerun as wr
from repro.kernels.matern_score.ops import matern_score
from repro.runtime.stream import StreamingBayesSplitEdge, requests_from_trace
from repro.wireless.traces import MIXED_TRACE_ARCHS, arrival_trace


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A described-chip compile can be written to the persistent cache
    but not read back without the chip, so keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def _trace(n, archs):
    return arrival_trace("replay", n=n, seed=0, budgets=(6, 10, 14, 20),
                         archs=archs)


def test_whole_run_compiles_at_64_cnn_lanes(one_chip):
    """The one-dispatch whole run over 64 mixed VGG19/ResNet101 lanes."""
    eng = WholeRunBayesSplitEdge(
        requests_from_trace(_trace(64, ("vgg19", "resnet101"))),
        EngineConfig(warm_start=False, compact=False))
    stacked = eng._stacked()
    assert stacked["budget"].shape[0] == 64
    grid = jnp.asarray(eng.grid, jnp.float32)
    wvec = wr.acq_wvec(eng.weights)
    compiled = wr.whole_run.lower(
        _shapes(stacked, one_chip), _shapes(grid, one_chip),
        _shapes(wvec, one_chip), eng.run_config()).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9


def test_stream_phase_compiles_at_32_lanes(one_chip):
    """One serving-loop dispatch of a 32-lane pool over the CNN + LM mix,
    at the final (64-point) GP bucket."""
    feed = requests_from_trace(_trace(32, MIXED_TRACE_ARCHS))
    eng = StreamingBayesSplitEdge(feed, EngineConfig(warm_start=False),
                                  n_lanes=32)
    staged = [eng._stage_request(i, sc) for i, sc in enumerate(feed)]
    stacked = wr.stack_staged(staged, eng.l_pad, 32)
    state, pen = jax.eval_shape(
        lambda s, g: wr.admit_init(s, g, eng.cfg, False), stacked, eng.grid)
    run_data = dict(params=stacked["params"], boundary=stacked["boundary"],
                    budget=stacked["budget"], pen=pen)
    m = wr._final_bucket(eng.cfg)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = wr.stream_phase.lower(
        _shapes(run_data, one_chip), _shapes(state, one_chip), i32, i32,
        _shapes(eng.grid, one_chip), _shapes(eng.wvec, one_chip),
        eng.cfg, m, True).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def test_matern_score_kernel_compiles(one_chip):
    """The Pallas Matérn-score kernel at S=16 scenarios, n=64 GP points
    and N=4160 candidates (64x64 grid plus refinement seeds)."""
    S, n, N = 16, 64, 4160
    f32 = jnp.float32
    sd = lambda *s: jax.ShapeDtypeStruct(s, f32, sharding=one_chip)  # noqa: E731
    compiled = matern_score.lower(
        sd(S, N, 2), sd(S, n, 2), sd(S, n), sd(S, n), sd(S), sd(S),
        interpret=False, use_ref=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
