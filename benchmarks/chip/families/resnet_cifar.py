"""CIFAR ResNet family: the model as the system under test runs it, the
clients' synthetic images, and the model FLOPs of one image.

Data: the Mod-CIFAR non-IID split of Zhao et al. (arXiv:1806.00582), as the
paper's Task 1 uses it. Client k's label distribution is
``q_k = (1 - gamma) p + gamma onehot(k mod C)`` with ``p`` uniform and
``gamma = EMD C / (2 (C - 1))``, so the mean client EMD is the traffic's
``emd``. Each client holds ``samples_per_client`` images whose labels follow
``q_k`` exactly (largest remainders), shuffled. An image is its class's
smooth prototype (a random 4x4 field per channel, bilinearly upsampled and
scaled to a peak of 1) plus Gaussian noise of scale ``noise``: made on the
device in one jitted call from the seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.tasks import softmax_xent
from repro.models import resnet

# The system's ResNet normalises with GroupNorm over this many groups.
SYSTEM_NORM_GROUPS = 8


def program(cfg):
    """(init_fn, loss_fn) of the system under test for this configuration."""
    if cfg["norm_groups"] != SYSTEM_NORM_GROUPS:
        raise ValueError(f"the system's ResNet uses GroupNorm({SYSTEM_NORM_GROUPS}), "
                         f"the configuration states {cfg['norm_groups']}")
    depth, widths, classes = cfg["depth"], tuple(cfg["widths"]), cfg["num_classes"]

    def init_fn(key):
        return resnet.init_resnet(key, num_classes=classes, depth=depth, widths=widths)

    def loss_fn(params, batch):
        x, y = batch
        return softmax_xent(resnet.resnet_forward(params, x, depth=depth, widths=widths), y)

    return init_fn, loss_fn


def client_labels(num_clients: int, per_client: int, classes: int, emd: float,
                  rng: np.random.Generator) -> np.ndarray:
    """(clients, per_client) int32 labels on the Mod-CIFAR split."""
    gamma = emd * classes / (2.0 * (classes - 1))
    if not 0.0 <= gamma <= 1.0:
        raise ValueError(f"EMD {emd} is not reachable with {classes} classes")
    out = np.empty((num_clients, per_client), np.int32)
    for k in range(num_clients):
        q = np.full(classes, (1.0 - gamma) / classes)
        q[k % classes] += gamma
        want = np.floor(q * per_client).astype(int)
        for c in np.argsort(-(q * per_client - want))[: per_client - want.sum()]:
            want[c] += 1
        out[k] = rng.permutation(np.repeat(np.arange(classes), want))
    return out


@functools.partial(jax.jit, static_argnames=("image_shape", "classes"))
def _images(key, labels, noise, *, image_shape, classes):
    h, w, c = image_shape
    kp, kn = jax.random.split(key)
    coarse = jax.random.normal(kp, (classes, 4, 4, c))
    protos = jax.image.resize(coarse, (classes, h, w, c), "bilinear")
    protos = protos / jnp.max(jnp.abs(protos), axis=(1, 2, 3), keepdims=True)
    return protos[labels] + noise * jax.random.normal(kn, labels.shape + (h, w, c))


def make_pools(cfg, traffic, seed: int):
    """Device arrays (images [K, P, H, W, C] float32, labels [K, P] int32)."""
    rng = np.random.default_rng([seed, 17])
    classes = cfg["num_classes"]
    labels = client_labels(traffic["clients"], traffic["samples_per_client"], classes,
                           traffic["emd"], rng)
    labels = jnp.asarray(labels)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 17)
    images = _images(key, labels, jnp.float32(traffic["noise"]),
                     image_shape=tuple(cfg["image_shape"]), classes=classes)
    return images, labels


def _taps(size: int, k: int, stride: int) -> int:
    """(output position, kernel tap) pairs along one axis of a SAME
    convolution that read an input inside the image: XLA's count, which
    leaves out the taps that fall on the padding."""
    out = -(-size // stride)
    lo = max((out - 1) * stride + k - size, 0) // 2
    return sum(0 <= o * stride + t - lo < size for o in range(out) for t in range(k))


def forward_flops(cfg, traffic) -> float:
    """Multiply-add FLOPs (2 per MAC) of the convolutions and the head for
    one image, counting the kernel taps that read inside the image;
    normalisation and activations are left out."""
    h, w, c = cfg["image_shape"]
    cin = cfg["widths"][0]
    macs = _taps(h, 3, 1) * _taps(w, 3, 1) * c * cin            # stem
    for s, cout in enumerate(cfg["widths"]):
        for b in range((cfg["depth"] - 2) // 6):
            stride = 2 if (s > 0 and b == 0) else 1
            macs += _taps(h, 3, stride) * _taps(w, 3, stride) * cin * cout
            if stride != 1 or cin != cout:                      # 1x1 projection
                macs += _taps(h, 1, stride) * _taps(w, 1, stride) * cin * cout
            h, w = -(-h // stride), -(-w // stride)
            macs += _taps(h, 3, 1) * _taps(w, 3, 1) * cout * cout
            cin = cout
    macs += cin * cfg["num_classes"]                             # head
    return 2.0 * macs
