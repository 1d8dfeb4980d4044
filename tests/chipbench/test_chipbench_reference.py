"""The chip benchmark's plain reference on the CPU at tiny sizes: the
system's vmap round agrees with it, and the comparison that decides
``correct`` fails when the round is computed in bfloat16 (the precision
control) or on half of each client's batch."""

from __future__ import annotations

import ast

import chipbench_tiny as ct
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return ct.tiny_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="module", params=sorted(ct.TINY_CELLS))
def readings(request, root):
    """(cell, program, f32 reference, bf16 control, half-batch reference)."""
    cell, seed = harness.load_cell(root, request.param), 2147483711
    _, _, _, prog, rounds = harness.start(cell, seed)
    ref = harness.reference_trajectory(cell, seed, rounds)
    ctl = harness.reference_trajectory(cell, seed, rounds, dtype=jnp.bfloat16)
    half = harness.reference_trajectory(cell, seed, rounds, batch_share=0.5)
    return cell, prog, ref, ctl, half


def test_program_round_agrees_with_the_reference(readings):
    cell, prog, ref, _, _ = readings
    numbers = harness.compare(prog, ref)
    ok, checks = harness.judge(numbers, cell.limits)
    assert ok, checks
    assert numbers["upload_gap"] == 0
    # the reference starts from the system's own initial weights
    assert all((a == b).all() for a, b in zip(prog.theta0, ref.theta0, strict=True))


def test_comparison_fails_in_bfloat16(readings):
    cell, _, ref, ctl, _ = readings
    ok, checks = harness.judge(harness.compare(ctl, ref), cell.limits)
    assert not ok, checks


def test_comparison_fails_on_half_the_batch(readings):
    cell, _, ref, _, half = readings
    ok, checks = harness.judge(harness.compare(half, ref), cell.limits)
    assert not ok, checks


def test_reference_imports_nothing_of_the_system():
    for path in (ct.REPO / "benchmarks" / "chip" / "reference").glob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            names = ([a.name for a in node.names] if isinstance(node, ast.Import) else
                     [node.module or ""] if isinstance(node, ast.ImportFrom) else [])
            assert not any(n.split(".")[0] in ("repro", "benchmarks") for n in names), \
                (path.name, names)


def test_reference_round_sends_exactly_k_per_leaf():
    import numpy as np

    from benchmarks.chip.reference.fl_round import RoundReference, num_keep

    rng = np.random.default_rng(0)
    params = [rng.normal(size=s).astype(np.float32) for s in ((3, 3, 4, 8), (8,), (40,))]
    rr = RoundReference(params, {"scheme": "dgcwgmf", "rate": 0.1, "tau": 0.6, "lr": 0.1})
    grads = lambda j, p: [rng.normal(size=x.shape).astype(np.float32) for x in p]
    for _ in range(3):
        out = rr.round([0, 3], grads)
        assert list(out["upload"]) == [sum(num_keep(x.size, 0.1) for x in params)] * 2
