"""Pallas kernels vs pure-jnp oracles: shape/dtype/parameter sweeps.

Kernels run in interpret mode on CPU (semantics identical to TPU lowering
modulo float association order → tolerances 1e-5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="dev extra not installed")
from hypothesis import given, settings, strategies as st

from repro.kernels import gmf_compress as gk
from repro.kernels import ops, ref

TOL = dict(rtol=1e-5, atol=1e-6)

SHAPES = [(5,), (128,), (1000,), (65_536,), (513, 257), (3, 5, 129), (8, 8, 8, 9)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("alpha", [0.0, 0.5, 0.9])
def test_momentum_correction_matches_ref(shape, alpha):
    key = jax.random.PRNGKey(0)
    u = jax.random.normal(key, shape)
    v = jax.random.normal(jax.random.fold_in(key, 1), shape)
    g = jax.random.normal(jax.random.fold_in(key, 2), shape)
    uk, vk = gk.momentum_correction_flat(u, v, g, alpha, interpret=True)
    ur, vr = ref.momentum_correction_leaf(u, v, g, alpha)
    np.testing.assert_allclose(uk, ur, **TOL)
    np.testing.assert_allclose(vk, vr, **TOL)


@pytest.mark.parametrize("shape", SHAPES)
def test_mask_apply_matches_ref(shape):
    key = jax.random.PRNGKey(1)
    u = jax.random.normal(key, shape)
    v = jax.random.normal(jax.random.fold_in(key, 1), shape)
    mask = (jax.random.uniform(jax.random.fold_in(key, 2), shape) > 0.7).astype(
        jnp.float32
    )
    out_k = gk.apply_mask_flat(u, v, mask, interpret=True)
    out_r = ref.apply_mask_update_leaf(u, v, mask)
    for a, b in zip(out_k, out_r, strict=True):
        np.testing.assert_allclose(a, b, **TOL)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(min_value=3, max_value=200_000),
    tau=st.floats(min_value=0.0, max_value=1.0),
    thr=st.floats(min_value=1e-6, max_value=0.1),
    seed=st.integers(min_value=0, max_value=1000),
)
def test_gmf_fused_matches_ref_property(n, tau, thr, seed):
    key = jax.random.PRNGKey(seed)
    u = jax.random.normal(key, (n,))
    v = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    m = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    nv = 1.0 / (jnp.linalg.norm(v) + 1e-16)
    nm = 1.0 / (jnp.linalg.norm(m) + 1e-16)
    out_k = gk.gmf_compress_flat(
        u, v, m, inv_norm_v=nv, inv_norm_m=nm, tau=tau, threshold=thr, interpret=True
    )
    out_r = ref.gmf_compress_leaf(
        u, v, m, inv_norm_v=nv, inv_norm_m=nm, tau=tau, threshold=thr
    )
    for a, b in zip(out_k, out_r, strict=True):
        np.testing.assert_allclose(a, b, **TOL)


def test_ops_pytree_wrappers_match_ref():
    key = jax.random.PRNGKey(2)
    tree = lambda k: {
        "a": jax.random.normal(jax.random.fold_in(key, k), (257,)),
        "nested": {"b": jax.random.normal(jax.random.fold_in(key, k + 10), (33, 5))},
    }
    u, v, g = tree(0), tree(1), tree(2)
    uk, vk = ops.momentum_correction(u, v, g, 0.9)
    ur, vr = ref.momentum_correction(u, v, g, 0.9)
    for got, want in ((uk, ur), (vk, vr)):
        np.testing.assert_allclose(got["a"], want["a"], **TOL)
        np.testing.assert_allclose(got["nested"]["b"], want["nested"]["b"], **TOL)


def test_kernels_inside_jit_and_grad_path():
    """use_kernels=True route must be jit-compatible end to end."""
    from repro.core import CompressionConfig, client_compress, init_states
    from repro.utils import tree_zeros_like

    params = {"w": jnp.zeros((4096,))}
    cfg = CompressionConfig(scheme="dgcwgmf", rate=0.1, tau=0.3, use_kernels=True)
    cfg_ref = CompressionConfig(scheme="dgcwgmf", rate=0.1, tau=0.3, use_kernels=False)
    grad = {"w": jax.random.normal(jax.random.PRNGKey(0), (4096,))}
    gbar = tree_zeros_like(params)

    @jax.jit
    def run(cfg_flag_grad):
        cstate, _ = init_states(cfg, params)
        return client_compress(cfg, cstate, cfg_flag_grad, gbar, 0)[0]

    G_k = run(grad)
    cstate, _ = init_states(cfg_ref, params)
    G_r, _, _ = client_compress(cfg_ref, cstate, grad, gbar, 0)
    np.testing.assert_allclose(G_k["w"], G_r["w"], **TOL)


def test_fused_exact_selector_sends_k_per_leaf():
    """The fused path sends exactly k = ceil(rate·n) entries per leaf, as
    the staged path does: its mask comes from the top-k's own indices, not
    from a score recomputed against the threshold."""
    from repro.core import CompressionConfig
    from repro.core.registry import resolve
    from repro.core.sparsify import num_keep
    from repro.core.state import ClientState

    rng = np.random.default_rng(0)
    shapes = [(3, 3, 16, 16), (3, 3, 32, 32), (3, 3, 64, 64), (64, 10), (16,)]

    def tree(scale=1.0):
        return {f"l{i}": jnp.asarray(scale * rng.standard_normal(s), jnp.float32)
                for i, s in enumerate(shapes)}

    state = ClientState(u=tree(), v=tree(), m=tree())
    grad, gbar = tree(), tree(0.1)
    k = sum(num_keep(int(np.prod(s)), 0.1) for s in shapes)
    for use_kernels in (False, True):
        scheme = resolve(CompressionConfig(scheme="dgcwgmf", rate=0.1, tau=0.6,
                                           use_kernels=use_kernels))
        _, new, info = jax.jit(
            lambda s, g, gb, scheme=scheme: scheme.client_compress(s, g, gb, 3)
        )(state, grad, gbar)
        assert int(info.upload_nnz) == k, use_kernels
        for leaf, shape in zip(jax.tree.leaves(new.v), shapes, strict=True):
            assert int(np.sum(np.asarray(leaf) == 0)) == num_keep(
                int(np.prod(shape)), 0.1)


def test_padding_never_selected():
    """Padded lanes (v=m=0 ⇒ z=0) must not enter the mask for thr>0."""
    n = 100  # heavily padded up to 65536
    v = jnp.ones((n,))
    u = jnp.ones((n,))
    m = jnp.ones((n,))
    g, u2, v2, mask = gk.gmf_compress_flat(
        u, v, m, inv_norm_v=0.1, inv_norm_m=0.1, tau=0.5, threshold=1e-6, interpret=True
    )
    assert g.shape == (n,)
    assert int(mask.sum()) == n  # all real elements selected, no padding leak
