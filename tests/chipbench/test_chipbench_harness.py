"""The chip benchmark's harness on the CPU at tiny sizes: the command's
refusal without a TPU, a whole run past that refusal, the data-driven
loading of a new cell from files alone, and the inputs it makes."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import chipbench_tiny as ct
import numpy as np
import pytest
from chipbench_token_mlp import token_mlp_root

from benchmarks.chip import harness

REPO = ct.REPO
LSTM = "lstm_tiny-dgcwgmf-3of6"
E2E = {"round_ms", "round_ms_p90", "wire_mb_per_round", "setup_s"}


def _command(cwd, *extra):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *spec["command"][1:], *extra], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu_and_names_the_platform():
    p = _command(REPO, "--workload", "lstm_shakespeare-dgcwgmf-10of100", "--seed", "2147483701",
                 "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr


def test_command_refuses_in_a_directory_of_only_the_benchmark(tmp_path):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(REPO / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path, "--workload", spec["workloads"][0]["name"], "--seed", "1",
                 "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return ct.tiny_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("trace", [False, True])
def test_tiny_run_is_correct_and_reports_the_cells_metrics(root, trace):
    r = ct.run_cell(root, LSTM, seed=2147483703, seconds=0.5, trace=trace)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == (ct.per_layer_names(ct.read_spec(root), LSTM) if trace else E2E)
    assert all(np.isfinite(m["value"]) for m in r["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(r["device"])
    if trace:
        assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
        assert {"device_ops", "idle_gaps"} == set(r["breakdown"])
        assert len(r["breakdown"]["device_ops"]) <= 10
    # the numbers compared come last, each beside its limit
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"bcast_gap", "delta_gap", "state_gap", "upload_gap",
                                "download_gap"}


def test_wire_is_counted_over_the_same_rounds_whatever_the_window(root):
    """The wire counts the traffic's ``wire_rounds`` first window rounds:
    a longer window, holding more rounds, reads the same number."""
    short = ct.run_cell(root, LSTM, seed=2147483705, seconds=0.05)
    long = ct.run_cell(root, LSTM, seed=2147483705, seconds=0.6)
    wire_rounds = harness.load_cell(root, LSTM).traffic["wire_rounds"]
    assert wire_rounds <= short["attempted"] < long["attempted"]
    assert short["metrics"]["wire_mb_per_round"] == long["metrics"]["wire_mb_per_round"]


@pytest.mark.parametrize("make_root", [ct.tiny_root, token_mlp_root],
                         ids=["alone", "beside_a_new_family"])
def test_a_new_cell_needs_only_files_and_an_entry(tmp_path, make_root):
    """``resnet56_cifar-none-20x64`` (scheme ``none``, no compression) from
    the configuration's entry, a traffic file, a limits file and a
    BENCHMARK.json entry; and the same kind of cell at a tiny size run
    through the harness. The same holds in a checkout that also holds a
    third family with a metric of its own."""
    none_20x64 = json.loads(
        (REPO / "benchmarks/chip/traffic/dgcwgmf-20x64.json").read_text())
    none_20x64.update(scheme="none")
    for k in ("rate", "tau"):
        none_20x64.pop(k)
    tiny_none = dict(ct.TINY_TRAFFIC["dgcwgmf-4x8"], scheme="none")
    resnet56 = json.loads((REPO / "benchmarks/chip/configs/resnet56_cifar.json").read_text())
    root = make_root(
        tmp_path, configs={"resnet56_cifar": resnet56,
                           "resnet8_tiny": ct.TINY_CONFIGS["resnet8_tiny"]},
        traffic={"none-20x64": none_20x64, "none-4x8": tiny_none},
        cells={"resnet56_cifar-none-20x64": ("resnet56_cifar", "none-20x64"),
               "resnet8_tiny-none-4x8": ("resnet8_tiny", "none-4x8")},
        limits={k: v for k, v in ct.TINY_LIMITS.items() if not k.startswith("state")})
    cell = harness.load_cell(root, "resnet56_cifar-none-20x64")
    assert cell.traffic["scheme"] == "none" and cell.config["depth"] == 56
    assert {m["name"] for m in cell.per_layer} == ct.family_per_layer(ct.read_spec(), REPO,
                                                                      "resnet_cifar")
    r = ct.run_cell(root, "resnet8_tiny-none-4x8", seed=7, seconds=0.3)
    assert r["correct"] is True, r["checks"]
    assert "state_gap" not in r["checks"]           # no compression state
    # dense: every client uploads every entry, the broadcast goes dense
    import jax

    init_fn, _ = harness.program_fns(harness.load_cell(root, "resnet8_tiny-none-4x8"))
    n = sum(x.size for x in jax.tree.leaves(jax.eval_shape(init_fn, jax.random.PRNGKey(0))))
    assert r["metrics"]["wire_mb_per_round"]["value"] == pytest.approx(2 * 4 * n * 4 / 1e6)


def test_same_seed_same_inputs_and_first_rounds_rows_all_differ(root):
    cell = harness.load_cell(root, LSTM)
    family = cell.module("families", cell.family)
    t = cell.traffic
    a = [np.asarray(x) for x in family.make_pools(cell.config, t, 2147483709)]
    b = [np.asarray(x) for x in family.make_pools(cell.config, t, 2147483709)]
    c = [np.asarray(x) for x in family.make_pools(cell.config, t, 2147483710)]
    assert all(np.array_equal(x, y) for x, y in zip(a, b, strict=True))
    assert not np.array_equal(a[0], c[0])
    assert a[0].shape == (t["clients"], t["samples_per_client"], t["seq_len"])
    assert np.array_equal(a[0][..., 1:], a[1][..., :-1])   # targets are the next characters

    batches = harness.Batches(family.make_pools(cell.config, t, 3), t["batch"], 3)
    seen = {c: set() for c in range(t["clients"])}
    ids = np.array([0, 2, 5])
    for r in range(harness.FIRST_ROUNDS):
        x = np.asarray(batches(r, ids, None)[0])
        for j, c in enumerate(ids):
            rows = {tuple(row) for row in x[j]}
            assert not rows & seen[c]
            seen[c] |= rows


def test_resnet_clients_follow_the_mod_cifar_split():
    from benchmarks.chip.harness import Cell

    family = Cell(name="", root=REPO, chips=1, config={}, traffic={}, limits={},
                  end_to_end=[], per_layer=[]).module("families", "resnet_cifar")
    labels = family.client_labels(20, 2500, 10, 1.35, np.random.default_rng(0))
    hist = np.stack([np.bincount(row, minlength=10) / 2500 for row in labels])
    emd = np.abs(hist - hist.mean(axis=0)).sum(axis=1).mean()
    assert emd == pytest.approx(1.35, abs=1e-3)
