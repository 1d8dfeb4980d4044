"""A third tiny model family for the chip benchmark's tests: a token-bag MLP
whose hidden layer has a scope of its own, with its plain reference,
configuration, traffic, limits and cell, and a reader of that scope whose
entry lists only the new cell; all added to a tiny checkout by new files and
new ``BENCHMARK.json`` entries alone."""

from __future__ import annotations

import json
import textwrap
from pathlib import Path

import chipbench_tiny as ct

FAMILY = "token_mlp"
CELL = "token_mlp_tiny-tokens-4x8"
METRIC = "token_hidden_ms"

CONFIG = {"family": FAMILY, "vocab_size": 32, "embed_dim": 16, "hidden_size": 24,
          "num_classes": 5, "parameters": 32 * 16 + 16 * 24 + 24 + 24 * 5 + 5,
          "matmul_precision": "default"}
TRAFFIC = {"scheme": "dgcwgmf", "rate": 0.1, "tau": 0.6, "lr": 0.1, "clients": 4,
           "cohort": 4, "batch": 8, "seq_len": 6, "samples_per_client": 32, "wire_rounds": 2}

# The system's side: the model as the program runs it, with its hidden
# layer under a scope of its own, the clients' data and the FLOP count.
FAMILY_SRC = '''
"""Token-bag MLP: the mean of a sequence's token embeddings, one tanh
hidden layer under the ``token_mlp.hidden`` scope, and a linear head."""

import functools

import jax
import jax.numpy as jnp

from repro.fl.tasks import softmax_xent


def _dense(key, n_in, n_out):
    return {"kernel": jax.random.normal(key, (n_in, n_out)) * n_in ** -0.5,
            "bias": jnp.zeros((n_out,))}


def program(cfg):
    def init_fn(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"embed": jax.random.normal(k1, (cfg["vocab_size"], cfg["embed_dim"])) * 0.1,
                "hidden": _dense(k2, cfg["embed_dim"], cfg["hidden_size"]),
                "head": _dense(k3, cfg["hidden_size"], cfg["num_classes"])}

    def loss_fn(params, batch):
        x, y = batch
        h = params["embed"][x].mean(axis=-2)
        with jax.named_scope("token_mlp.hidden"):
            h = jnp.tanh(h @ params["hidden"]["kernel"] + params["hidden"]["bias"])
        return softmax_xent(h @ params["head"]["kernel"] + params["head"]["bias"], y)

    return init_fn, loss_fn


@functools.partial(jax.jit, static_argnames=("shape", "vocab", "classes"))
def _pools(key, *, shape, vocab, classes):
    kx, ky = jax.random.split(key)
    return (jax.random.randint(kx, shape, 0, vocab),
            jax.random.randint(ky, shape[:-1], 0, classes))


def make_pools(cfg, traffic, seed):
    """Device arrays (tokens [K, P, L] int32, labels [K, P] int32)."""
    shape = (traffic["clients"], traffic["samples_per_client"], traffic["seq_len"])
    return _pools(jax.random.PRNGKey(seed), shape=shape, vocab=cfg["vocab_size"],
                  classes=cfg["num_classes"])


def forward_flops(cfg, traffic):
    return 2.0 * cfg["hidden_size"] * (cfg["embed_dim"] + cfg["num_classes"])
'''

# The plain reference: the same model and initial weights, written anew,
# importing nothing of the system.
REFERENCE_SRC = '''
"""Plain float32 token-bag MLP; the same initial weights as the system's."""

import jax
import jax.numpy as jnp


def _dense(key, n_in, n_out):
    return {"kernel": jax.random.normal(key, (n_in, n_out)) * n_in ** -0.5,
            "bias": jnp.zeros((n_out,))}


def init(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"embed": jax.random.normal(k1, (cfg["vocab_size"], cfg["embed_dim"])) * 0.1,
            "hidden": _dense(k2, cfg["embed_dim"], cfg["hidden_size"]),
            "head": _dense(k3, cfg["hidden_size"], cfg["num_classes"])}


def loss(params, batch, cfg, dtype, precision, keep=None):
    x, y = batch
    p = jax.tree.map(lambda w: w.astype(dtype), params)
    h = jnp.mean(p["embed"][x], axis=-2)
    h = jnp.tanh(jnp.dot(h, p["hidden"]["kernel"], precision=precision) + p["hidden"]["bias"])
    logits = jnp.dot(h, p["head"]["kernel"], precision=precision) + p["head"]["bias"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1)[:keep])
'''

READER_SRC = '''
"""Device time per round of the ops under the ``token_mlp.hidden`` scope."""


def read(ctx):
    ns = ctx.view.scope_ns("token_mlp.hidden")
    return ns * 1e-6 / ctx.rounds if ns else None
'''


def token_mlp_root(tmp: Path, configs=None, traffic=None, cells=None, limits=None) -> Path:
    """``chipbench_tiny.tiny_root`` with the token-bag MLP family, its cell
    and its metric beside the given cells."""
    configs = ct.TINY_CONFIGS if configs is None else configs
    traffic = ct.TINY_TRAFFIC if traffic is None else traffic
    cells = ct.TINY_CELLS if cells is None else cells
    root = ct.tiny_root(tmp, configs={**configs, "token_mlp_tiny": CONFIG},
                        traffic={**traffic, "tokens-4x8": TRAFFIC},
                        cells={**cells, CELL: ("token_mlp_tiny", "tokens-4x8")}, limits=limits)
    bench = root / "benchmarks" / "chip"
    for kind, src in (("families", FAMILY_SRC), ("reference", REFERENCE_SRC)):
        (bench / kind / f"{FAMILY}.py").write_text(textwrap.dedent(src))
    (bench / "metrics" / f"{METRIC}.py").write_text(textwrap.dedent(READER_SRC))
    (bench / "limits" / f"{CELL}.json").write_text(json.dumps(ct.TINY_LIMITS))
    spec = ct.read_spec(root)
    spec["per_layer"].append({"name": METRIC, "unit": "ms", "better": "lower",
                              "source": "device_trace", "layer": "token hidden",
                              "moves": "round_ms", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
