"""Plain reference of a federated round of the compression schemes the
benchmark's traffic names, written from the paper's equations (Algorithm 1
of arXiv:2211.09320, with DGC's momentum correction and error feedback).

Imports nothing of the system under test. Host numpy in float32, one
client at a time; the gradients come from the configuration's plain model
(``reference/<family>.py``) through ``grad_of``.

Per sampled client k, on each parameter leaf (per-tensor selection):

    dgcwgmf   U <- alpha U + g          (momentum correction)
              V <- V + U                (error feedback)
              M <- beta M + G_prev      (global momentum from the broadcast)
              Z  = |(1 - tau) V/(|V| + eps) + tau M/(|M| + eps)|
              mask = the k = ceil(rate n) largest entries of Z
              G_k = V mask;  U <- U (1 - mask);  V <- V (1 - mask)
    dgc       the same with Z = |V| and no M
    none      G_k = g (dense)

Server: G = sum_k G_k / cohort; the broadcast is G, its non-zeros are the
download count, and every parameter moves by -lr G.
"""

from __future__ import annotations

import math

import numpy as np

SCHEMES = ("dgcwgmf", "dgc", "none")


def num_keep(n: int, rate: float) -> int:
    return max(1, min(n, int(math.ceil(rate * n))))


def topk_mask(z: np.ndarray, rate: float) -> np.ndarray:
    """1 where ``z`` is among its ``ceil(rate n)`` largest entries (ties at
    the k-th value included), else 0."""
    flat = z.reshape(-1)
    k = num_keep(flat.size, rate)
    thr = np.partition(flat, flat.size - k)[flat.size - k]
    return (z >= thr).astype(np.float32)


def _unit(x: np.ndarray, eps: float) -> np.ndarray:
    return x / (np.sqrt(np.sum(np.square(x))) + np.float32(eps))


class RoundReference:
    """The server and every client's compression state, from zero.

    ``params`` is the list of float32 parameter leaves; ``traffic`` the
    cell's traffic mix (scheme, rate, tau, alpha, beta, lr, eps)."""

    def __init__(self, params: list[np.ndarray], traffic: dict):
        scheme = traffic["scheme"]
        if scheme not in SCHEMES:
            raise ValueError(f"reference round knows {SCHEMES}, not {scheme!r}")
        self.scheme = scheme
        self.rate = float(traffic.get("rate", 1.0))
        self.tau = float(traffic.get("tau", 0.0)) if scheme == "dgcwgmf" else 0.0
        self.alpha = np.float32(traffic.get("alpha", 0.9))
        self.beta = np.float32(traffic.get("beta", 0.9))
        self.lr = np.float32(traffic["lr"])
        self.eps = float(traffic.get("eps", 1e-16))
        self.params = [np.asarray(p, np.float32) for p in params]
        self.gbar = [np.zeros_like(p) for p in self.params]
        self.states: dict[int, dict[str, list[np.ndarray]]] = {}

    def state(self, client: int) -> dict[str, list[np.ndarray]]:
        if client not in self.states:
            self.states[client] = {
                f: [np.zeros_like(p) for p in self.params] for f in "uvm"}
        return self.states[client]

    def _compress(self, client: int, grads: list[np.ndarray]):
        """One client's payload and its upload count."""
        if self.scheme == "none":
            return grads, sum(g.size for g in grads)
        st = self.state(client)
        out, sent = [], 0
        for i, g in enumerate(grads):
            u = self.alpha * st["u"][i] + g
            v = st["v"][i] + u
            if self.scheme == "dgcwgmf":
                m = self.beta * st["m"][i] + self.gbar[i]
                st["m"][i] = m
                z = np.abs(np.float32(1.0 - self.tau) * _unit(v, self.eps)
                           + np.float32(self.tau) * _unit(m, self.eps))
            else:
                z = np.abs(v)
            mask = topk_mask(z, self.rate)
            keep = np.float32(1.0) - mask
            out.append(v * mask)
            st["u"][i], st["v"][i] = u * keep, v * keep
            sent += int(mask.sum())
        return out, sent

    def round(self, client_ids, grad_of) -> dict:
        """One round over the sampled ``client_ids``; ``grad_of(j, params)``
        gives the j-th sampled client's gradient leaves at ``params``.
        Returns the clients' raw mean gradient, the broadcast, the upload
        counts and the download count."""
        total = [np.zeros_like(p) for p in self.params]
        raw = [np.zeros_like(p) for p in self.params]
        upload = []
        for j, c in enumerate(client_ids):
            grads = [np.asarray(g, np.float32) for g in grad_of(j, self.params)]
            payload, sent = self._compress(int(c), grads)
            for i in range(len(total)):
                total[i] += payload[i]
                raw[i] += grads[i]
            upload.append(sent)
        n = np.float32(len(client_ids))
        self.gbar = [t / n for t in total]
        self.params = [p - self.lr * g for p, g in zip(self.params, self.gbar,
                                                        strict=True)]
        return {"raw_grad": [r / n for r in raw],
                "bcast": [g.copy() for g in self.gbar],
                "upload": np.asarray(upload, np.int64),
                "download": int(sum(np.count_nonzero(g) for g in self.gbar))}
