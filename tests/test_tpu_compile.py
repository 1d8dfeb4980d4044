"""The FL round's Pallas kernels compiled for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
unaligned slices, too much VMEM, shapes it cannot tile. Here the TPU
compiler installed with jaxlib compiles each kernel with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology, at
ResNet-56's largest leaf (3·3·64·64), at a 1M-element leaf, and vmapped
over a 20-client cohort as the round runs it. Nothing executes, so no chip
is needed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gmf_compress as gk

LEAVES = {
    "resnet56_largest": (3, 3, 64, 64),
    "1m": (1024, 1024),
}


def _gmf(u, v, m, inv_nv, inv_nm, tau, thr):
    return gk.gmf_compress_flat(u, v, m, inv_norm_v=inv_nv, inv_norm_m=inv_nm,
                                tau=tau, threshold=thr, interpret=False)


KERNELS = {
    # name -> (fn, number of leaf-shaped operands, number of scalars)
    "gmf_compress": (_gmf, 3, 4),
    "momentum_correction": (
        lambda u, v, g: gk.momentum_correction_flat(u, v, g, 0.9,
                                                    interpret=False), 3, 0),
    "apply_mask": (
        lambda u, v, mask: gk.apply_mask_flat(u, v, mask, interpret=False),
        3, 0),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compiled_text(fn, shape, n_leaf, n_scalar, sharding):
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding)
    args = [leaf] * n_leaf + [scalar] * n_scalar
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("leaf", sorted(LEAVES))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_natively_for_v5e(one_chip, no_persistent_cache,
                                          kernel, leaf):
    fn, n_leaf, n_scalar = KERNELS[kernel]
    text = _compiled_text(fn, LEAVES[leaf], n_leaf, n_scalar, one_chip)
    assert "tpu_custom_call" in text


def test_vmapped_gmf_compress_compiles_natively_for_v5e(one_chip,
                                                        no_persistent_cache):
    """The round vmaps the fused pass over the cohort (per-client scalars)."""
    clients = 20
    shape = (clients, *LEAVES["resnet56_largest"])
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((clients,), jnp.float32, sharding=one_chip)
    text = jax.jit(jax.vmap(_gmf)).lower(
        leaf, leaf, leaf, scalar, scalar, scalar, scalar).compile().as_text()
    assert "tpu_custom_call" in text


def test_vmapped_fused_client_compress_compiles_natively_for_v5e(
        one_chip, no_persistent_cache, monkeypatch):
    """The round's fused GMF compression under the exact selector (top-k
    mask applied by the Pallas kernel), vmapped over the cohort."""
    from repro.core import CompressionConfig
    from repro.core.registry import resolve
    from repro.core.state import ClientState
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    scheme = resolve(CompressionConfig(scheme="dgcwgmf", rate=0.1, tau=0.6,
                                       use_kernels=True))
    shape = LEAVES["resnet56_largest"]
    leaf = {"w": jax.ShapeDtypeStruct((20, *shape), jnp.float32,
                                      sharding=one_chip)}
    gbar = {"w": jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)}

    def compress(state, grad, gbar_prev):
        return scheme.client_compress(state, grad, gbar_prev, 0)

    text = jax.jit(jax.vmap(compress, in_axes=(0, 0, None))).lower(
        ClientState(u=leaf, v=leaf, m=leaf), leaf, gbar).compile().as_text()
    assert "tpu_custom_call" in text
