"""The program names its layers where the benchmark reads them: device
scopes in the HLO that a profiler trace carries of every program a cell
runs, and the set-up's host span."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import scopes
from repro.core import engine as engine_mod
from repro.core.engine import WalkEngine
from repro.core.graphs import from_edges
from repro.core.transition import MHLJParams
from repro.models import regression
from repro.walk_sgd import fleet as fleet_mod

N, W, STEPS, DIM = 24, 8, 3, 3
FLEET_SCOPES = {"walk_transition", "fleet_sgd", "fleet_average", "fleet_loss_eval"}


def _graph():
    src = np.arange(N)
    return from_edges(N, np.concatenate([src, src]),
                      np.concatenate([(src + 1) % N, (src + 5) % N]), layout="ragged")


def _engine(backend):
    return WalkEngine.from_graph(_graph(), MHLJParams(p_j=0.2, p_d=0.5, r=3),
                                 lipschitz=jnp.linspace(1.0, 3.0, N), layout="ragged",
                                 backend=backend)


def _traced_programs(trace_dir, call):
    """``scopes.program_scopes`` of a profiler trace of ``call()``, run once
    before the trace so that the trace holds the run and not the compile."""
    jax.block_until_ready(call())
    jax.profiler.start_trace(str(trace_dir))
    try:
        jax.block_until_ready(call())
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(found) == 1
    return scopes.program_scopes(found[0])


def _tops(programs, prefix):
    """Top scopes of each program named ``prefix...`` that the trace holds
    (it holds every program the process has loaded, not only those run)."""
    found = [{scopes.top_scope(p) for p in table.values()}
             for name, table in programs.items() if name.startswith(prefix)]
    assert found
    return found


@pytest.mark.parametrize("backend", ["scan", "pallas"])
def test_walk_run_carries_the_transition_scope(backend, tmp_path):
    engine = _engine(backend)
    call = jax.jit(lambda e, key, v0: e.run(key, v0, STEPS))
    programs = _traced_programs(
        tmp_path, lambda: call(engine, jax.random.key(0), jnp.arange(W, dtype=jnp.int32)))
    assert any(engine_mod.WALK_TRANSITION_SCOPE in tops
               for tops in _tops(programs, "jit__lambda("))


def test_fleet_scan_carries_every_fleet_scope(tmp_path):
    fleet = fleet_mod.WalkFleet.create(_engine("scan"), W, v0s=np.arange(W), avg_every=2)
    args = (jnp.zeros((W, DIM)), jnp.ones((N, DIM)), jnp.ones((N,)), jnp.ones((N,)))
    programs = _traced_programs(tmp_path, lambda: fleet_mod.run_fleet(
        jax.random.key(1), *args, fleet, STEPS, 0.1, jnp.full((STEPS,), 0.2), True,
        regression.linear_grad))
    assert any(FLEET_SCOPES <= tops for tops in _tops(programs, "jit__fleet_scan("))
    assert {fleet_mod.FLEET_SGD_SCOPE, fleet_mod.FLEET_AVERAGE_SCOPE,
            fleet_mod.FLEET_LOSS_EVAL_SCOPE} == FLEET_SCOPES - {"walk_transition"}


def test_edge_cdf_build_reports_its_span_and_chunks():
    g = _graph()
    durations, events = [], []

    def on_duration(event, duration, **_):
        durations.append((event, duration))

    def on_event(event, **_):
        events.append(event)

    span = engine_mod.EDGE_CDF_BUILD_SPAN
    before = engine_mod.span_seconds.get(span, 0.0)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        engine_mod.ragged_edge_cdf(g.indptr, g.indices, g.degrees,
                                   lipschitz=jnp.ones(N), chunk_rows=5)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
    seconds = [d for e, d in durations if e == engine_mod.span_event(span)]
    assert len(seconds) == 1 and seconds[0] > 0
    assert engine_mod.span_seconds[span] == pytest.approx(before + seconds[0])
    assert events.count(engine_mod.span_event(span, "chunks")) == -(-N // 5)


def test_set_up_reader_reads_the_engine_span_total(monkeypatch):
    from chipbench import harness

    read = harness._load_module("metrics", "edge_cdf_build_s.setup").read
    monkeypatch.setattr(engine_mod, "span_seconds", {})
    assert read({}) is None  # no engine built in this process
    g = _graph()
    engine_mod.ragged_edge_cdf(g.indptr, g.indices, g.degrees, lipschitz=jnp.ones(N))
    assert read({}) == engine_mod.span_seconds["edge_cdf_build"] > 0
    monkeypatch.delattr(engine_mod, "span_seconds")  # a program without the span
    assert read({}) is None
