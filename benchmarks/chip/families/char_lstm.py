"""Character-LSTM family: the model as the system under test runs it, the
clients' synthetic text, and the model FLOPs of one sequence.

Data: each client is a speaker with its own first-order Markov chain over
the vocabulary, a mixture of a shared chain and the client's own
(``client_mix`` of the latter), each row drawn from a Dirichlet; so clients
are non-IID as LEAF's Shakespeare split is by speaker. A client holds
``samples_per_client`` sequences of ``seq_len + 1`` characters; inputs are
the first ``seq_len``, targets the last. Made on the device in one jitted
call from the seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.fl.tasks import softmax_xent
from repro.models import lstm


def program(cfg):
    """(init_fn, loss_fn) of the system under test for this configuration."""
    if cfg["num_layers"] != 1:
        raise ValueError("the system's char-LSTM has one layer")

    def init_fn(key):
        return lstm.init_lstm(key, vocab=cfg["vocab_size"], embed_dim=cfg["embed_dim"],
                              hidden=cfg["hidden_size"])

    def loss_fn(params, batch):
        x, y = batch
        return softmax_xent(lstm.lstm_forward(params, x), y)

    return init_fn, loss_fn


@functools.partial(jax.jit, static_argnames=("clients", "per_client", "length", "vocab"))
def _chains(key, mix, alpha_shared, alpha_own, *, clients, per_client, length, vocab):
    k_shared, k_own, k_start, k_steps = jax.random.split(key, 4)
    shared = jax.random.dirichlet(k_shared, jnp.full((vocab,), alpha_shared), (vocab,))
    own = jax.random.dirichlet(k_own, jnp.full((vocab,), alpha_own), (clients, vocab))
    logp = jnp.log(jnp.maximum((1.0 - mix) * shared + mix * own, 1e-30))
    rows = jnp.arange(clients)[:, None]
    start = jax.random.randint(k_start, (clients, per_client), 0, vocab)

    def step(state, k):
        nxt = jax.random.categorical(k, logp[rows, state]).astype(jnp.int32)
        return nxt, nxt

    _, seq = jax.lax.scan(step, start, jax.random.split(k_steps, length))
    seq = jnp.concatenate([start[None], seq], axis=0)          # (L + 1, K, P)
    seq = jnp.moveaxis(seq, 0, -1)
    return seq[..., :-1], seq[..., 1:]


def make_pools(cfg, traffic, seed: int):
    """Device arrays (inputs [K, P, L] int32, targets [K, P, L] int32)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 17)
    return _chains(key, jnp.float32(traffic["client_mix"]),
                   jnp.float32(traffic["alpha_shared"]), jnp.float32(traffic["alpha_own"]),
                   clients=traffic["clients"], per_client=traffic["samples_per_client"],
                   length=traffic["seq_len"], vocab=cfg["vocab_size"])


def forward_flops(cfg, traffic) -> float:
    """Multiply-add FLOPs (2 per MAC) of the gate and head matmuls for one
    sequence; the embedding lookup and the gate nonlinearities are left
    out."""
    e, h, v = cfg["embed_dim"], cfg["hidden_size"], cfg["vocab_size"]
    return 2.0 * traffic["seq_len"] * (e * 4 * h + h * 4 * h + h * v)
