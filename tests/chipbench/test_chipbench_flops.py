"""The chip benchmark's FLOP counts against XLA's cost analysis of the
system's forward pass, on the CPU at the configurations' real widths, and
its table of peaks."""

from __future__ import annotations

import json

import chipbench_tiny as ct
import jax
import jax.numpy as jnp
import pytest

BENCH = ct.REPO / "benchmarks" / "chip"
CONFIG_FILES = {c["name"]: ct.REPO / c["file"] for c in ct.read_spec()["configs"]}


def _config(name):
    return json.loads(CONFIG_FILES[name].read_text())


def _family(name):
    from benchmarks.chip.harness import Cell

    return Cell(name="", root=ct.REPO, chips=1, config={}, traffic={}, limits={},
                end_to_end=[], per_layer=[]).module("families", name)


def _xla_flops(fn, *args) -> float:
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def test_resnet56_forward_flops_match_cost_analysis():
    cfg = _config("resnet56_cifar")
    init_fn, loss_fn = _family("resnet_cifar").program(cfg)
    from repro.models import resnet

    params = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, *cfg["image_shape"]), jnp.float32)
    xla = _xla_flops(lambda p, x: resnet.resnet_forward(p, x, depth=cfg["depth"],
                                                       widths=tuple(cfg["widths"])), params, x)
    ours = _family("resnet_cifar").forward_flops(cfg, {})
    # ours counts convolutions and the head; XLA adds the normalisation and
    # activations, a few percent at these widths.
    assert 0.9 * xla <= ours <= xla, (ours, xla)


def test_char_lstm_forward_flops_match_cost_analysis():
    cfg = _config("lstm_shakespeare")
    init_fn, _ = _family("char_lstm").program(cfg)
    from repro.models import lstm

    params = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    # XLA counts a scan's body once, so compare one step.
    tokens = jax.ShapeDtypeStruct((1, 1), jnp.int32)
    xla = _xla_flops(lstm.lstm_forward, params, tokens)
    ours = _family("char_lstm").forward_flops(cfg, {"seq_len": 1})
    assert 0.95 * xla <= ours <= xla, (ours, xla)


@pytest.mark.parametrize("name,family", [(name, _config(name)["family"])
                                         for name in CONFIG_FILES])
def test_configuration_states_its_parameter_count(name, family):
    cfg = _config(name)
    init_fn, _ = _family(family).program(cfg)
    params = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg["parameters"]


def test_peaks_table_names_its_source_and_refuses_unknown_devices(tmp_path):
    from benchmarks.chip import harness

    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "Google Cloud" in peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit) as e:
        harness.load_peak(ct.REPO, "TPU v99")
    assert e.value.code != 0
