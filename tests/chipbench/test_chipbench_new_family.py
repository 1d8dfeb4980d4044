"""A model family, its cell and a per-layer metric of its own enter the chip
benchmark through new files and new ``BENCHMARK.json`` entries alone: the
token-bag MLP of ``chipbench_token_mlp`` beside the tiny ResNet and LSTM
cells."""

from __future__ import annotations

import chipbench_tiny as ct
import numpy as np
import pytest
from chipbench_token_mlp import CELL, METRIC, token_mlp_root

from benchmarks.chip import harness

LSTM = "lstm_tiny-dgcwgmf-3of6"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return token_mlp_root(tmp_path_factory.mktemp("checkout"))


def _names(root, cell):
    return {m["name"] for m in harness.load_cell(root, cell).per_layer}


def test_the_new_metric_attaches_to_the_new_cell_only(root):
    """The new cell lists its own metric and nothing else: the entries the
    accepted cells share (``device_idle_share``, ``round_mfu``, ...) name
    their cells in ``workloads``, so a new family's cell gets none of them
    until its name is added there."""
    spec = ct.read_spec(root)
    assert _names(root, CELL) == ct.per_layer_names(spec, CELL) == {METRIC}
    for cell in set(ct.TINY_CELLS) - {LSTM}:       # the LSTM cell: the test below
        assert METRIC not in _names(root, cell)


def test_the_new_family_runs_correct_and_reports_its_metric(root):
    r = ct.run_cell(root, CELL, seed=2147483717, seconds=0.3, trace=True)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {METRIC}           # none of the shared entries
    assert 0 < r["metrics"][METRIC]["value"] < np.inf


def test_the_tiny_lstm_cell_neither_lists_nor_reports_the_new_metric(root):
    """A traced run reports what its cell lists
    (``test_tiny_run_is_correct_and_reports_the_cells_metrics[True]``), so
    the listing settles both."""
    spec = ct.read_spec(root)
    assert _names(root, LSTM) == ct.per_layer_names(spec, LSTM) == ct.family_per_layer(
        ct.read_spec(), ct.REPO, "char_lstm")
    assert METRIC not in ct.per_layer_names(spec, LSTM)
