"""Plain float32 reference of the character LSTM of McMahan et al. 2017
(federated Shakespeare): an embedding, one LSTM layer and a linear head over
the vocabulary, predicting the next character at every position.

Straightforward jax.numpy. Gates in the order input, forget, cell, output;
the forget gate carries a +1 bias, as the configuration's system has it.
Imports nothing of the system under test. ``init`` draws the initial
weights from the key in the same way as the system does, so that both start
from one point given one seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def init(key, cfg):
    v, e, h = cfg["vocab_size"], cfg["embed_dim"], cfg["hidden_size"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "embed": jax.random.normal(k1, (v, e)) * 0.1,
        "wx": jax.random.normal(k2, (e, 4 * h)) * e ** -0.5,
        "wh": jax.random.normal(k3, (h, 4 * h)) * h ** -0.5,
        "b": jnp.zeros((4 * h,)),
        "head": {"kernel": jax.random.normal(k4, (h, v)) * h ** -0.5,
                 "bias": jnp.zeros((v,))},
    }


def forward(params, tokens, precision):
    """Logits (batch, time, vocab) for int tokens (batch, time)."""
    x = params["embed"][tokens]                      # (B, T, E)
    h0 = jnp.zeros((tokens.shape[0], params["wh"].shape[0]), params["wx"].dtype)

    def step(carry, x_t):
        h, c = carry
        gates = (jnp.dot(x_t, params["wx"], precision=precision)
                 + jnp.dot(h, params["wh"], precision=precision) + params["b"])
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    _, hs = lax.scan(step, (h0, h0), jnp.swapaxes(x, 0, 1))
    hs = jnp.swapaxes(hs, 0, 1)                      # (B, T, H)
    head = params["head"]
    return jnp.dot(hs, head["kernel"], precision=precision) + head["bias"]




def loss(params, batch, cfg, dtype, precision, keep=None):
    """Mean next-character cross-entropy of one client's batch, over its
    first ``keep`` sequences (all where None), at matmul ``precision``. A
    ``dtype`` below float32 casts the weights to it and runs the model
    there (the precision control)."""
    x, y = batch
    p = jax.tree.map(lambda w: w.astype(dtype), params)
    logits = forward(p, x, precision).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)
    return jnp.mean(nll[:keep])
