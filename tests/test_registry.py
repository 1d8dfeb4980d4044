"""Scheme-registry completeness: every registered preset must compose into
a working scheme under vmap (the FL engines' client axis), the documented
degeneracies must hold for the composed implementations, and FetchSGD
through the ordinary round engine must reproduce the retired
``FetchSGDSimulator``'s ledger numbers (golden fixture)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    PRESETS,
    CompressionConfig,
    available_presets,
    client_compress,
    init_states,
    resolve,
    server_aggregate,
)
from repro.core import stages
from repro.utils import tree_map, tree_zeros_like

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "golden"))

PARAMS = {"w": jnp.zeros((40, 8)), "b": jnp.zeros((24,))}
CLIENTS = 3


def _grads(t):
    key = jax.random.fold_in(jax.random.PRNGKey(5), t)
    return {
        "w": jax.random.normal(key, (CLIENTS, 40, 8)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (CLIENTS, 24)),
    }


@pytest.mark.parametrize("preset", available_presets())
def test_preset_round_trips_under_vmap(preset):
    """client_compress (vmapped over clients) -> sum -> server_aggregate,
    two rounds, exactly the engines' data flow — every preset, including
    the sketch-based fetchsgd, must produce finite payloads and sane
    accounting."""
    cfg = CompressionConfig(scheme=preset, rate=0.2, tau=0.3,
                            sketch_cols=256, sketch_rows=3)
    scheme = resolve(cfg)
    cstate1, sstate = init_states(cfg, PARAMS)
    cstates = tree_map(
        lambda x: jnp.broadcast_to(x, (CLIENTS,) + x.shape), cstate1)
    gbar = tree_zeros_like(PARAMS)
    total = sum(x.size for x in jax.tree_util.tree_leaves(PARAMS))
    for t in range(2):
        G, cstates, infos = jax.vmap(
            lambda st, g, tt=t: client_compress(cfg, st, g, gbar, tt)
        )(cstates, _grads(t))
        g_sum = tree_map(lambda x: jnp.sum(x, axis=0), G)
        gbar, sstate, ainfo = server_aggregate(
            cfg, sstate, g_sum, float(CLIENTS),
            lr=jnp.asarray(0.1), params=PARAMS)
        # broadcast is always param-shaped, whatever the upload payload was
        assert jax.tree_util.tree_structure(gbar) == jax.tree_util.tree_structure(PARAMS)
        for leaf in jax.tree_util.tree_leaves(gbar):
            assert bool(jnp.all(jnp.isfinite(leaf)))
        assert float(infos.total_params[0]) == total
        assert 0 < float(ainfo.download_nnz) <= max(total, float(infos.upload_nnz[0]))
    # structural properties agree between config delegation and the scheme
    assert cfg.uses_u == scheme.uses_u
    assert cfg.server_momentum == scheme.server_momentum


def test_registry_and_presets_consistent():
    for name, spec in PRESETS.items():
        assert spec.selector in stages.REGISTRY["selector"], name
        assert spec.compensator in stages.REGISTRY["compensator"], name
        assert spec.fusion in stages.REGISTRY["fusion"], name
        assert spec.wire == "auto" or spec.wire in stages.REGISTRY["wire"], name


def test_dgcwgmf_tau0_equals_dgc_composed():
    cfg_f = CompressionConfig(scheme="dgcwgmf", rate=0.1, tau=0.0)
    cfg_d = CompressionConfig(scheme="dgc", rate=0.1)
    cs_f, _ = init_states(cfg_f, PARAMS)
    cs_d, _ = init_states(cfg_d, PARAMS)
    gbar = tree_map(lambda x: x + 0.05, tree_zeros_like(PARAMS))
    for t in range(3):
        g = {k: v[0] for k, v in _grads(t).items()}
        Gf, cs_f, _ = client_compress(cfg_f, cs_f, g, gbar, t)
        Gd, cs_d, _ = client_compress(cfg_d, cs_d, g, gbar, t)
        for k in Gf:
            np.testing.assert_array_equal(np.asarray(Gf[k]), np.asarray(Gd[k]))


def test_rate_one_equals_none_composed():
    """rate=1.0 top-k keeps every entry — payload identical to the dense
    preset (top-k selection is scale-invariant, so the fusion score cannot
    drop anything at rate 1)."""
    cfg_t = CompressionConfig(scheme="topk", rate=1.0)
    cfg_n = CompressionConfig(scheme="none")
    cs_t, _ = init_states(cfg_t, PARAMS)
    cs_n, _ = init_states(cfg_n, PARAMS)
    gbar = tree_zeros_like(PARAMS)
    for t in range(2):
        g = {k: v[0] for k, v in _grads(t).items()}
        Gt, cs_t, it = client_compress(cfg_t, cs_t, g, gbar, t)
        Gn, cs_n, inn = client_compress(cfg_n, cs_n, g, gbar, t)
        for k in Gt:
            np.testing.assert_array_equal(np.asarray(Gt[k]), np.asarray(Gn[k]))
        assert float(it.upload_nnz) == float(inn.upload_nnz)


def test_stage_overrides_compose():
    """A preset with an overridden stage resolves to the overridden spec and
    actually changes behaviour (randomk selection ignores magnitudes)."""
    base = CompressionConfig(scheme="dgc", rate=0.2)
    hybrid = CompressionConfig(scheme="dgc", rate=0.2, selector_stage="randomk")
    assert resolve(hybrid).selector.name == "randomk"
    assert resolve(hybrid).compensator.name == "dgc"
    g = {k: v[0] for k, v in _grads(0).items()}
    gbar = tree_zeros_like(PARAMS)
    cs_b, _ = init_states(base, PARAMS)
    cs_h, _ = init_states(hybrid, PARAMS)
    Gb, _, _ = client_compress(base, cs_b, g, gbar, 0)
    Gh, _, _ = client_compress(hybrid, cs_h, g, gbar, 0)
    assert any(
        float(jnp.sum(jnp.abs(Gb[k] - Gh[k]))) > 0 for k in Gb)


def test_unknown_names_rejected_with_registry_listing():
    with pytest.raises(ValueError, match="registered presets"):
        CompressionConfig(scheme="nope")
    with pytest.raises(ValueError, match="registered selectors"):
        CompressionConfig(scheme="dgc", selector_stage="nope")
    with pytest.raises(ValueError, match="registered fusions"):
        CompressionConfig(scheme="dgc", fusion_stage="nope")


def test_custom_preset_registration(registry_sandbox):
    """The README's worked example: registering a new composition makes it a
    first-class scheme (CLI choices, CompressionConfig validation, engines)."""
    from repro.core import SchemeSpec, register_preset

    name = "_test_topk_ef"
    register_preset(name, SchemeSpec(selector="topk", compensator="ef"),
                    doc="top-k with plain error feedback (test)")
    assert name in available_presets()
    # a just-registered preset validates and resolves immediately
    cfg_new = CompressionConfig(scheme=name, rate=0.2)
    assert resolve(cfg_new).compensator.name == "ef"
    # the same composition is also reachable without registration via
    # per-config stage overrides
    cfg = CompressionConfig(scheme="topk", compensator_stage="ef", rate=0.2)
    cs, _ = init_states(cfg, PARAMS)
    gbar = tree_zeros_like(PARAMS)
    g = {k: v[0] for k, v in _grads(0).items()}
    G, cs, info = client_compress(cfg, cs, g, gbar, 0)
    # error feedback engaged: the residual survives in V
    assert any(float(jnp.sum(jnp.abs(v))) > 0 for v in cs.v.values())


def test_duplicate_registration_raises(registry_sandbox):
    """Silent shadowing of a registered stage/preset is a footgun: a
    duplicate name must raise, and override=True is the explicit escape
    hatch that replaces it."""
    from repro.core import SchemeSpec, register_preset
    from repro.core.stages import Selector, register

    with pytest.raises(ValueError, match="override=True"):
        @register("selector", "topk")
        class ShadowTopK(Selector):  # pragma: no cover - never registered
            pass

    @register("selector", "topk", override=True)
    class ReplacementTopK(Selector):
        name = "topk"

    from repro.core.stages import get_stage
    assert isinstance(get_stage("selector", "topk"), ReplacementTopK)

    register_preset("_test_dup", SchemeSpec(selector="topk"))
    with pytest.raises(ValueError, match="override=True"):
        register_preset("_test_dup", SchemeSpec(selector="randomk"))
    register_preset("_test_dup", SchemeSpec(selector="randomk"),
                    override=True)
    assert PRESETS["_test_dup"].selector == "randomk"


def test_register_unknown_stage_kind_raises():
    from repro.core.stages import register

    with pytest.raises(ValueError, match="unknown stage kind"):
        register("not_a_kind", "x")


def test_use_kernels_respects_composed_stages():
    """The fused Pallas path implements exactly topk+dgc+gmf; other
    compositions under use_kernels must take the staged path, not be
    silently replaced by the kernel's semantics (or worse, dropped)."""
    gbar = tree_map(lambda x: x + 0.05, tree_zeros_like(PARAMS))
    g = {k: v[0] for k, v in _grads(0).items()}
    # ef compensator (no U): kernel path would have produced an empty payload
    cfg = CompressionConfig(scheme="gmc", fusion_stage="gmf", use_kernels=True)
    cs, _ = init_states(cfg, PARAMS)
    G, cs, info = client_compress(cfg, cs, g, gbar, 0)
    assert float(info.upload_nnz) > 0
    assert any(float(jnp.sum(jnp.abs(leaf))) > 0
               for leaf in jax.tree_util.tree_leaves(G))
    # randomk selector: selection rule must not change with use_kernels
    for t in range(2):
        outs = []
        for kern in (False, True):
            cfg = CompressionConfig(scheme="dgcwgmf", selector_stage="randomk",
                                    rate=0.2, use_kernels=kern)
            cs, _ = init_states(cfg, PARAMS)
            G, _, info = client_compress(cfg, cs, g, gbar, t)
            outs.append((G, float(info.upload_nnz)))
        (Ga, na), (Gb, nb) = outs
        assert na == nb
        for k in Ga:
            np.testing.assert_allclose(np.asarray(Ga[k]), np.asarray(Gb[k]),
                                       rtol=1e-5, atol=1e-6)


def test_reregistering_preset_invalidates_resolved_schemes(registry_sandbox):
    from repro.core import SchemeSpec, register_preset

    name = "_test_mutable"
    register_preset(name, SchemeSpec(selector="topk"))
    cfg = CompressionConfig(scheme=name)
    assert resolve(cfg).compensator.name == "none"
    register_preset(name, SchemeSpec(selector="topk", compensator="ef"),
                    override=True)
    assert resolve(cfg).compensator.name == "ef"


# ---------------------------------------------------------------------------
# FetchSGD parity vs the retired FetchSGDSimulator (golden fixture)
# ---------------------------------------------------------------------------


def test_fetchsgd_matches_retired_simulator_golden():
    """FetchSGD through FLSimulator/RoundEngine must reproduce the retired
    ``FetchSGDSimulator``'s ledger numbers EXACTLY (sketch upload bytes,
    k-sparse download bytes, per-round totals) and its accuracy/params to
    float tolerance, on the same task/seed
    (tests/golden/fetchsgd_golden.npz, captured pre-refactor).

    The fixture was captured under JAX 0.4.37, whose threefry PRNG was not
    yet partitionable (the default since JAX 0.5); the run reproduces those
    draws — initial weights and sketch hashes — with that setting."""
    from tiny_task import GoldenTask

    from repro.fl import FLConfig, FLSimulator

    golden = np.load(os.path.join(
        os.path.dirname(__file__), "golden", "fetchsgd_golden.npz"))
    fl = FLConfig(num_clients=4, rounds=6, batch_size=12, learning_rate=0.1,
                  eval_every=2, seed=0)
    comp = CompressionConfig(scheme="fetchsgd", sketch_rows=3, sketch_cols=128,
                             sketch_k_frac=0.05, sketch_momentum=0.9)
    with jax.threefry_partitionable(False):
        task = GoldenTask(seed=0)
        sim = FLSimulator(fl, comp, task.init_fn, task.loss_fn, task.eval_fn)
        sim.run(task.batch_provider())

    assert sim.ledger.upload_bytes == float(golden["upload_bytes"])
    assert sim.ledger.download_bytes == float(golden["download_bytes"])
    assert sim.ledger.rounds == int(golden["rounds"])
    np.testing.assert_allclose(
        [r["comm_gb"] for r in sim.history], golden["comm_gb_per_round"],
        rtol=0, atol=1e-15)
    np.testing.assert_allclose(np.asarray(sim.params["w"]), golden["params/w"],
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sim.params["b"]), golden["params/b"],
                               rtol=0, atol=1e-6)
    assert abs(sim.final_accuracy() - float(golden["final_accuracy"])) < 1e-6
