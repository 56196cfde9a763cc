"""The lane pool's readback (``wholerun.fetch_out`` / ``take_rows``) and
its two callers: the stream's ``_LanePool.collect`` and the offline
compacted run's ``scatter_rows``.

A served 32-lane pool retires every subset size from 1 to 32 rows, with
faulted rows beside them. Each caller must hand back bitwise the rows a
per-key device gather ``state[k][rows]`` reads, leave the faulted (or
padding) rows out, and build no XLA program once its first call is done:
the fetch has one shape per pool width, whatever the number of rows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Scenario, default_vgg19_problem
from repro.core import wholerun as wr
from repro.core.engine_config import EngineConfig
from repro.runtime.stream import StreamingBayesSplitEdge

WIDTH = 32
KEYS = wr._OUT_KEYS


class _Programs:
    """XLA programs built while entered (``jax.monitoring``)."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def _on(self, event, duration, **_):
        self.n += event == self._COMPILE

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def _reqs():
    return [Scenario(default_vgg19_problem(), seed=s, budget=10 + s % 3)
            for s in range(WIDTH)]


@pytest.fixture(scope="module")
def served():
    """A 32-lane pool with every lane admitted and one phase run, so that
    some lanes have retired and the rest are mid-solve."""
    reqs = _reqs()
    eng = StreamingBayesSplitEdge(reqs, EngineConfig(warm_start=False),
                                  n_lanes=WIDTH, budget_max=12)
    pool = eng._pools[0]
    pool.admit(list(enumerate(reqs)))
    pool.dispatch(draining=True)
    jax.block_until_ready(pool.state)
    return eng, pool, dict(pool.state), reqs


def _subsets():
    """Retiring rows and faulted rows for each subset size 1..32."""
    for k in range(1, WIDTH + 1):
        perm = np.random.default_rng(k).permutation(WIDTH)
        yield sorted(perm[:k].tolist()), sorted(perm[k:k + 2].tolist())


def _rig(state, rows, faulted):
    """The pool state with ``rows`` retired and ``faulted`` faulted."""
    lanes = np.arange(WIDTH)
    return dict(state,
                active=state["active"] & jnp.asarray(~np.isin(lanes, rows)),
                fault=state["fault"] | jnp.asarray(np.isin(lanes, faulted)))


@jax.jit
def _gather(state, idx):
    """The device gather ``state[k][rows]`` the readback replaces, as a
    program of the test's own: it never builds one that a per-count
    readback under test could then reuse."""
    return jax.tree.map(lambda v: v[idx],
                        dict({k: state[k] for k in KEYS},
                             theta=state["theta"]))


def _stream(served, state, rows, faulted):
    eng, pool, _, reqs = served
    pool.state = state
    pool.order[:] = -1
    for r in rows + faulted:
        pool.order[r] = r
        eng._requests[r] = reqs[r]
    out, left, _ = pool.collect()
    assert left == faulted
    assert [res.lane for res in out] == rows
    assert all(pool.order[r] == -1 for r in rows)
    assert all(pool.order[r] == r for r in faulted)
    return {k: np.stack([res.raw[k] for res in out]) for k in KEYS}


def _offline(served, state, rows, faulted):
    # faulted rows stand in for padding lanes, which the scatter drops
    order = np.full(WIDTH, -1, np.int64)
    order[rows] = np.arange(len(rows))
    final: dict = {}
    wr.scatter_rows(final, state, rows + faulted, order, len(rows))
    assert set(final) == set(KEYS) | {"theta"}
    return final


def _bitwise(a, b):
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


@pytest.mark.parametrize("caller", [_stream, _offline],
                         ids=["stream", "offline"])
def test_readback_rows_are_the_device_gather_and_build_no_program(
        served, caller):
    _, _, state0, _ = served
    built = []
    for rows, faulted in _subsets():
        state = jax.block_until_ready(_rig(state0, rows, faulted))
        with _Programs() as progs:
            got = caller(served, state, rows, faulted)
        built.append(progs.n)
        want = jax.device_get(_gather(state, jnp.asarray(rows)))
        for k in KEYS:
            assert _bitwise(got[k], want[k]), (caller.__name__, len(rows), k)
        if "theta" in got:
            for k in wr._THETA_KEYS:
                assert _bitwise(got["theta"][k], want["theta"][k])
    assert built[1:] == [0] * (WIDTH - 1), built


def test_take_rows_copies_out_of_the_snapshot(served):
    _, _, state, _ = served
    snap = wr.fetch_out(state, theta=True, it=jnp.int32(3))
    assert int(snap["it"]) == 3 and snap["active"].shape == (WIDTH,)
    sub = wr.take_rows(snap, [1, 5])
    for k in KEYS:
        assert not np.shares_memory(sub[k], snap[k])
        assert _bitwise(sub[k], snap[k][[1, 5]])
    for k in wr._THETA_KEYS:
        assert not np.shares_memory(sub["theta"][k], snap["theta"][k])
