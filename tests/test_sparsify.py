"""Top-k selection: exactness, sampled-estimator bounds (hypothesis)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="dev extra not installed")
from hypothesis import given, settings, strategies as st

from repro.core import sparsify


def test_num_keep_bounds():
    assert sparsify.num_keep(100, 0.1) == 10
    assert sparsify.num_keep(5, 0.001) == 1  # at least one element
    assert sparsify.num_keep(10, 1.0) == 10
    with pytest.raises(ValueError):
        sparsify.num_keep(10, 0.0)


def test_exact_mask_density():
    z = jax.random.normal(jax.random.PRNGKey(0), (10_000,))
    mask = sparsify.topk_masks([z], 0.1, "exact")[0]
    assert int(mask.sum()) == 1000


def test_exact_mask_selects_largest():
    z = jnp.asarray([0.1, -5.0, 0.3, 2.0, -0.2, 0.05])
    mask = sparsify.topk_masks([z], 0.34, "exact")[0]  # keep 2+
    assert mask[1] == 1.0 and mask[3] == 1.0  # |−5| and |2| are top-2


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(min_value=64, max_value=50_000),
    rate=st.floats(min_value=0.01, max_value=0.9),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_sampled_estimator_density_bound(n, rate, seed):
    """Sampled-threshold nnz stays within a reasonable factor of target."""
    z = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    mask = sparsify.topk_masks([z], rate, "sampled")[0]
    target = sparsify.num_keep(n, rate)
    nnz = int(mask.sum())
    # strided sample of a Gaussian: quantile error shrinks with sample size;
    # allow a generous 2.5x band plus small-n slack.
    assert nnz <= max(2.5 * target, target + 64)
    assert nnz >= max(1, int(0.3 * target) - 64)


@settings(max_examples=10, deadline=None)
@given(rate=st.floats(min_value=0.05, max_value=0.5))
def test_global_topk_total_density(rate):
    leaves = [
        jax.random.normal(jax.random.PRNGKey(1), (300,)),
        jax.random.normal(jax.random.PRNGKey(2), (17, 11)),
        jax.random.normal(jax.random.PRNGKey(3), (64, 8)),
    ]
    masks = sparsify.global_topk_masks(leaves, rate)
    total = sum(x.size for x in leaves)
    nnz = sum(int(m.sum()) for m in masks)
    assert nnz == sparsify.num_keep(total, rate)


def test_mask_jit_and_vmap():
    z = jax.random.normal(jax.random.PRNGKey(0), (8, 1000))
    f = jax.jit(jax.vmap(lambda x: sparsify.topk_masks([x], 0.1, "exact")[0]))
    masks = f(z)
    np.testing.assert_array_equal(np.asarray(masks.sum(axis=1)), 100 * np.ones(8))


# --- the exact threshold search -------------------------------------------

SEARCH_CASES = ("random", "coarse_ties", "all_equal", "half_zeros", "inf",
                "subnormal", "k_1", "k_n", "below_one_tile")


def _search_case(name):
    """(non-negative float32 scores, k) for one edge case; 5000 entries is
    past one (8, 128) tile and not a whole number of them."""
    rng = np.random.default_rng(SEARCH_CASES.index(name))
    n, k = 5000, 500
    z = np.abs(rng.standard_normal(n)).astype(np.float32)
    if name == "coarse_ties":
        z = np.round(z * 2) / 2
    elif name == "all_equal":
        z[:] = 0.75
    elif name == "half_zeros":
        z[: n // 2] = 0.0
    elif name == "inf":
        z[rng.random(n) < 0.2] = np.inf
    elif name == "subnormal":
        z *= np.float32(1e-39)
    elif name == "k_1":
        k = 1
    elif name == "k_n":
        k = n
    elif name == "below_one_tile":
        z, k = z[:37], 4
    return jnp.asarray(z), k


def _bits(x):
    return np.asarray(x, np.float32).view(np.int32)


@pytest.mark.parametrize("case", SEARCH_CASES)
def test_search_equals_top_k_bitwise(case):
    """The bit-pattern search returns lax.top_k's k-th value bit for bit:
    under jit with a static k, with a traced k, and under vmap."""
    z, k = _search_case(case)
    want = jax.lax.top_k(z, k)[0][-1]
    static = jax.jit(sparsify.exact_threshold, static_argnums=1)(z, k)
    traced = jax.jit(sparsify.exact_threshold)(z, jnp.int32(k))
    assert _bits(static) == _bits(want)
    assert _bits(traced) == _bits(want)
    rows = jnp.stack([z, z[::-1] * 0.5, jnp.roll(z, 3) * 2.0])
    got = jax.jit(jax.vmap(lambda r: sparsify.exact_threshold(r, k)))(rows)
    np.testing.assert_array_equal(_bits(got), _bits(jax.lax.top_k(rows, k)[0][:, -1]))


def _model_shapes(model):
    from repro.models import lstm, resnet

    key = jax.random.PRNGKey(0)
    if model == "resnet56":
        params = jax.eval_shape(lambda: resnet.init_resnet(key, depth=56))
    else:
        params = jax.eval_shape(lambda: lstm.init_lstm(key, 80))
    return [x.shape for x in jax.tree_util.tree_leaves(params)]


@pytest.mark.parametrize("model,groups,leaves", [("resnet56", 13, 169), ("lstm", 6, 6)])
def test_select_plan_groups_leaves_by_size(model, groups, leaves):
    shapes = _model_shapes(model)
    plan = sparsify.select_groups(shapes)
    assert (len(plan), len(shapes)) == (groups, leaves)
    assert sorted(i for ix in plan for i in ix) == list(range(leaves))
    for ix in plan:
        assert len({int(np.prod(shapes[i])) for i in ix}) == 1


@pytest.mark.parametrize("rate_kind", ["static", "traced"])
@pytest.mark.parametrize("model", ["resnet56", "lstm"])
def test_grouped_masks_equal_leaf_by_leaf_top_k(model, rate_kind):
    """One search per leaf size gives each leaf the mask of its own
    lax.top_k threshold (scores rounded coarsely, so ties at the k-th value
    occur). A dyadic rate gives the traced k the static one's value."""
    shapes = _model_shapes(model)
    keys = jax.random.split(jax.random.PRNGKey(1), len(shapes))
    zs = [jnp.round(jax.random.normal(kk, s) * 8) / 8 for kk, s in zip(keys, shapes)]
    rate = 0.125
    if rate_kind == "static":
        got = jax.jit(lambda zs: sparsify.topk_masks(zs, rate))(zs)
    else:
        got = jax.jit(sparsify.topk_masks)(zs, jnp.float32(rate))
    for z, mask in zip(zs, got, strict=True):
        za = jnp.abs(z)
        thr = jax.lax.top_k(za.reshape(-1), sparsify.num_keep(z.size, rate))[0][-1]
        np.testing.assert_array_equal(np.asarray(mask), np.asarray(za >= thr, np.float32))


def test_round_selects_without_a_sort_one_search_per_leaf_size():
    """The vmap round of a small ResNet under dgcwgmf lowers with no sort or
    top-k, and its compress.select scope runs one search loop that carries
    one threshold per client and leaf of each distinct leaf size."""
    from repro.core import CompressionConfig
    from repro.fl import FLConfig, FLSimulator
    from repro.fl.tasks import softmax_xent
    from repro.models import resnet

    depth, clients = 8, 2
    sim = FLSimulator(
        FLConfig(num_clients=clients, rounds=1, batch_size=2),
        CompressionConfig(scheme="dgcwgmf", rate=0.1),
        lambda key: resnet.init_resnet(key, depth=depth),
        lambda p, b: softmax_xent(resnet.resnet_forward(p, b[0], depth=depth), b[1]))
    batch = (jnp.zeros((clients, 2, 32, 32, 3)), jnp.zeros((clients, 2), jnp.int32))
    args = (sim.params, sim.cstates, sim.sstate, sim.gbar_prev, jnp.arange(clients),
            batch, jnp.asarray(0), jnp.float32(0.1), sim.tau_ctl.tau)
    text = sim.engine.round_fn.lower(*args).as_text()
    assert "stablehlo.sort" not in text and "top_k" not in text

    def walk(jaxpr, scope=""):
        for eqn in jaxpr.eqns:
            path = f"{scope}/{eqn.source_info.name_stack}"
            yield eqn, path
            for param in eqn.params.values():
                for sub in param if isinstance(param, (list, tuple)) else [param]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub, path)

    eqns = list(walk(jax.make_jaxpr(sim.engine.round_fn)(*args).jaxpr))
    assert not any(e.primitive.name in ("sort", "top_k") for e, _ in eqns)
    loops = [e for e, path in eqns
             if "compress.select" in path and e.primitive.name in ("scan", "while")]
    assert len(loops) == 1
    plan = sparsify.select_groups([x.shape for x in jax.tree_util.tree_leaves(sim.params)])
    carried = loops[0].params["jaxpr"].out_avals[1:loops[0].params["num_carry"]]
    assert sorted(a.shape for a in carried) == sorted((clients, len(ix)) for ix in plan)
