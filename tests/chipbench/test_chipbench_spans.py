"""The program's own spans and scopes as the chip benchmark reads them: a
tiny cell driven through ``FLSimulator.run`` under the CPU profiler and
reduced by ``trace_reduce.load``; each host phase of a round is one span,
the client-state copies and the top-k each have a scope, and the readers of
both return numbers."""

from __future__ import annotations

import glob
import os

import chipbench_tiny as ct
import jax
import numpy as np
import pytest

from benchmarks.chip import harness, trace_reduce

PHASES = ("fl.inputs", "fl.batches", "fl.dispatch", "fl.wait", "fl.account", "fl.on_round")
READERS = ("round_dispatch_ms", "round_host_ms", "client_state_ms", "client_select_ms",
           "round_unscoped_ms")
MIN_ROUNDS = 4
# Long enough that the coverage over all rounds rests on tens of rounds of
# the tiny LSTM cell, not on the few a single preemption can swing.
WINDOW_S = 1.0


def _phase_events(trace_dir: str) -> dict[str, list[tuple[float, float, int]]]:
    """Each ``fl.*`` span of the host's threads as (start, end, round id)."""
    (pb,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out: dict[str, list] = {name: [] for name in PHASES}
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in out:
                    out[e.name].append((e.start_ns, e.start_ns + e.duration_ns,
                                        dict(e.stats).get("round")))
    return {name: sorted(spans) for name, spans in out.items()}


@pytest.fixture(scope="module", params=sorted(ct.TINY_CELLS))
def traced(request, tmp_path_factory):
    root = ct.tiny_root(tmp_path_factory.mktemp("checkout"))
    cell = harness.load_cell(root, request.param)
    sim, batches, *_ = harness.start(cell, seed=2147483713)
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
        _, stamps = harness.run_rounds(sim, batches, WINDOW_S, MIN_ROUNDS)
    jax.profiler.stop_trace()
    hlo = harness.round_hlo(sim, batches)
    view = trace_reduce.load(d, hlo, harness.WINDOW_SPAN)
    # each call of ``run`` counts its rounds from 0
    return cell, view, hlo, _phase_events(d), list(range(len(stamps)))


def test_each_phase_is_one_span_per_round_with_its_round_id(traced):
    _, view, _, events, rounds = traced
    assert len(rounds) >= MIN_ROUNDS
    for name in PHASES:
        assert [r for _, _, r in events[name]] == rounds, name
        assert len(view.span_ns(name)) == len(rounds), name


def test_the_phases_cover_the_round_on_the_host(traced):
    """Between one round's callback and the next, at least 95% of the host's
    time lies inside one of the round's spans, in the median round and over
    all of them; the phases never overlap. (Outside every span lies the
    counts' readback, a few percent of a tiny round on the CPU, where one
    preemption of the process can land in a single round.)"""
    _, _, _, events, _ = traced
    spans = sorted(s for name in PHASES for s in events[name])
    for (_, end, _), (start, _, _) in zip(spans, spans[1:], strict=False):
        assert end <= start
    marks = [start for start, _, _ in events["fl.on_round"]]
    rounds = [(sum(min(e, b) - max(s, a) for s, e, _ in spans if e > a and s < b), b - a)
              for a, b in zip(marks, marks[1:], strict=False)]
    shares = [covered / length for covered, length in rounds]
    assert np.median(shares) >= 0.95, shares
    assert sum(c for c, _ in rounds) >= 0.95 * sum(n for _, n in rounds), shares


def test_the_round_program_names_the_client_state_and_the_top_k(traced):
    _, view, hlo, _, _ = traced
    program, paths = trace_reduce.hlo_scopes(hlo)
    assert program == view.program == "jit_round_fn"
    for scope in ("round.client_state", "compress.select"):
        assert any(scope in p for p in paths.values()), scope
    # the top-k lies inside the compress scope, so compress keeps its meaning
    select = [p for p in paths.values() if "compress.select" in p]
    assert all("round.client_compress" in p for p in select)
    assert view.scope_ns("compress.select") <= view.scope_ns("round.client_compress")


def test_the_new_readers_return_numbers(traced):
    cell, view, _, _, rounds = traced
    ctx = harness.LayerContext(view=view, rounds=len(rounds), round_ms=10.0,
                               flops_per_round=1e9, peak=ct.CPU_PEAK)
    got = {name: cell.module("metrics", name).read(ctx) for name in READERS}
    assert all(v is not None and np.isfinite(v) and v >= 0 for v in got.values()), got
    assert got["round_dispatch_ms"] > 0
    assert got["round_dispatch_ms"] <= got["round_host_ms"]


def test_the_new_readers_return_nothing_without_the_spans_and_scopes(traced):
    """A program without the spans and scopes (the parent commit's) reads
    as nothing, not as zero."""
    cell, view, _, _, rounds = traced
    bare = trace_reduce.TraceView(
        view.ops, [s for s in view.spans if not s.name.startswith("fl.")], view.window,
        {op: path for op, path in view.scopes.items()
         if "round.client_state" not in path and "compress.select" not in path},
        view.program, view.runs)
    ctx = harness.LayerContext(view=bare, rounds=len(rounds), round_ms=10.0,
                               flops_per_round=1e9, peak=ct.CPU_PEAK)
    assert {name: cell.module("metrics", name).read(ctx) for name in READERS} == dict.fromkeys(
        READERS)
