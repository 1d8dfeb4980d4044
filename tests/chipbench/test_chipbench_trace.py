"""The chip benchmark's trace reduction: scope attribution on a small trace
recorded on the CPU, and the busy, idle and breakdown arithmetic on a
trace whose intervals are known."""

from __future__ import annotations

import chipbench_tiny as ct
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import trace_reduce
from benchmarks.chip.trace_reduce import Op, Span, TraceView

SCOPES = ("round.client_grads", "round.client_compress", "round.server_aggregate",
          "round.apply_update")


def _round(x, w):
    with jax.named_scope("round.client_grads"):
        g = jnp.tanh(x @ w) @ w.T
    with jax.named_scope("round.client_compress"):
        z = jnp.sort(jnp.abs(g).reshape(-1))
        g = jnp.where(jnp.abs(g) >= z[-64], g, 0.0)
    with jax.named_scope("round.server_aggregate"):
        m = jnp.mean(g, axis=0)
    with jax.named_scope("round.apply_update"):
        return w - 0.1 * m[:, None]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A CPU trace of three 'rounds', each after a batch-building span."""
    fn = jax.jit(_round)
    x, w = jnp.ones((256, 256)), jnp.eye(256)
    hlo = fn.lower(x, w).compile().as_text()
    fn(x, w).block_until_ready()
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.batch_build"):
                x = x + 1.0
            w = fn(x, w)
            w.block_until_ready()
    jax.profiler.stop_trace()
    return trace_reduce.load(d, hlo, "bench.window")


def test_scope_paths_come_from_the_compiled_hlo():
    hlo = jax.jit(_round).lower(jnp.ones((8, 8)), jnp.eye(8)).compile().as_text()
    program, paths = trace_reduce.hlo_scopes(hlo)
    assert program == "jit__round"
    for scope in SCOPES:
        assert any(scope in p for p in paths.values()), scope


def test_cpu_trace_attributes_ops_to_their_scopes(recorded):
    view = recorded
    assert view.program == "jit__round"
    assert {op.module for op in view.ops} >= {"jit__round"}
    for scope in ("round.client_grads", "round.client_compress"):
        assert view.scope_ns(scope) > 0, scope
    # Every op of the program belongs to at most one of the round's scopes,
    # and the scoped time never exceeds the ops' own time.
    total = sum(op.end - op.start for op in view.ops if op.module == view.program)
    assert sum(view.scope_ns(s) for s in SCOPES) <= total + 1e-6
    assert len(view.span_ns("bench.batch_build")) == 3
    assert 0 < view.busy_ns() <= view.window_ns


def test_metric_readers_on_a_cpu_trace(recorded):
    from benchmarks.chip.harness import Cell, LayerContext

    cell = Cell(name="", root=ct.REPO, chips=1, config={}, traffic={}, limits={},
                end_to_end=[], per_layer=[])
    ctx = LayerContext(view=recorded, rounds=3, round_ms=10.0, flops_per_round=1e9,
                       peak={"bf16_flops_per_s": 1e12})
    read = lambda name: cell.module("metrics", name).read(ctx)
    assert read("client_grads_ms") == pytest.approx(
        recorded.scope_ns("round.client_grads") * 1e-6 / 3)
    assert read("device_idle_share") == pytest.approx(
        100 * (1 - recorded.busy_ns() / recorded.window_ns))
    assert read("batch_build_ms") > 0
    assert read("round_mfu") == pytest.approx(100 * 1e9 / (10e-3 * 1e12))


def test_busy_idle_and_breakdown_arithmetic():
    ops = [Op("fusion.1", "jit_round_fn", 0, 10), Op("sort.2", "jit_round_fn", 5, 15),
           Op("fusion.1", "jit_round_fn", 20, 30), Op("gather", "jit__take", 32, 34)]
    spans = [Span("bench.batch_build", 14, 22), Span("PjitFunction(round_fn)", 29, 33),
             Span("bench.batch_build", 33, 40)]
    view = TraceView(ops, spans, (0, 40), {"fusion.1": "jit(round_fn)/round.client_grads/conv",
                                           "sort.2": "jit(round_fn)/round.client_compress/sort"},
                     "jit_round_fn")
    assert view.busy_intervals() == [(0, 15), (20, 30), (32, 34)]
    assert view.busy_ns() == 27
    assert view.idle_gaps() == [(15, 20), (30, 32), (34, 40)]
    assert view.scope_ns("round.client_grads") == 20
    assert view.scope_ns("round.client_compress") == 10
    assert view.scope_ns("round.server_aggregate") == 0
    b = view.breakdown(top=2)
    assert b["device_ops"][0] == [
        "jit_round_fn:fusion.1 [jit(round_fn)/round.client_grads/conv]", pytest.approx(2e-8)]
    assert len(b["device_ops"]) == 2
    # the longest gaps first, each named by the innermost span open at its middle
    assert b["idle_gaps"] == [["bench.batch_build", pytest.approx(6e-9)],
                              ["bench.batch_build", pytest.approx(5e-9)]]
    assert view.host_label(31) == "PjitFunction(round_fn)"
    assert view.host_label(1) == "no host span open"
