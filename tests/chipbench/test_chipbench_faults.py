"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip, with the timed path broken underneath: ``correct`` has to come out
false for each fault a training cell can have on one chip."""

from __future__ import annotations

import chipbench_tiny as ct
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness

CELL = "resnet8_tiny-dgcwgmf-4x8"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return ct.tiny_root(tmp_path_factory.mktemp("checkout"))


def _state_unchanged(monkeypatch):
    """Every round returns the state it was given (its counts are real)."""
    build = harness.build_simulator

    def broken(cell, seed):
        sim = build(cell, seed)
        inner = sim._round_fn

        def round_fn(params, cstates, sstate, gbar_prev, *rest):
            out = inner(params, cstates, sstate, gbar_prev, *rest)
            return (params, cstates, sstate, gbar_prev, *out[4:])

        sim._round_fn = round_fn
        return sim

    monkeypatch.setattr(harness, "build_simulator", broken)


def _half_batch(monkeypatch):
    """Each client's loss leaves out half of its batch and means over the
    rest."""
    fns = harness.program_fns

    def broken(cell):
        init_fn, loss_fn = fns(cell)
        half = lambda b: jax.tree.map(lambda x: x[: x.shape[0] // 2], b)
        return init_fn, lambda p, b: loss_fn(p, half(b))

    monkeypatch.setattr(harness, "program_fns", broken)


def _bfloat16(monkeypatch):
    """The precision control planted in the program: the clients' model in
    bfloat16."""
    fns = harness.program_fns

    def broken(cell):
        init_fn, loss_fn = fns(cell)
        bf = lambda t: jax.tree.map(
            lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x, t)
        return init_fn, lambda p, b: loss_fn(bf(p), bf(b)).astype(jnp.float32)

    monkeypatch.setattr(harness, "program_fns", broken)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _bfloat16],
                         ids=["state_unchanged", "half_batch", "bfloat16"])
def test_broken_round_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    r = ct.run_cell(root, CELL, seed=2147483717, seconds=0.2)
    assert r["correct"] is False, r["checks"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
