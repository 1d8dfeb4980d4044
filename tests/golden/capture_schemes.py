"""Capture golden numerics for the compression schemes.

Run from the repo root:

    PYTHONPATH=src python tests/golden/capture_schemes.py

Writes ``tests/golden/schemes_golden.npz`` (client_compress /
server_aggregate outputs for every preset x selector x wire dtype over a
3-round, 2-client loop) and ``tests/golden/fetchsgd_golden.npz`` (ledger
numbers + final params of the FetchSGD reference simulator on the shared
tiny task).

The schemes fixture pins the registry compositions of ``repro.core`` as
they stand under JAX 0.9.0; the registry must reproduce it bit-exactly
(tests/test_golden_schemes.py). It was first captured from the monolithic
pre-registry implementation under JAX 0.4.37, whose registry port matched
it bit for bit; it was re-captured when the toolchain moved to JAX 0.9.0,
with the scheme code unchanged. Re-running this script must be a no-op
diff — that is the regression check.

The fetchsgd fixture was captured from ``repro.fl.fetchsgd``'s
``FetchSGDSimulator``, which was RETIRED in PR 3 (FetchSGD is now the
``fetchsgd`` registry preset running through the ordinary engines —
tests/test_registry.py pins its ledger numbers to this fixture). On any
current tree the guarded import below fails by design and the committed
``fetchsgd_golden.npz`` is kept as-is; recapturing it requires checking
out the PR-2 head.
"""

from __future__ import annotations

import os
import sys

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(__file__))

from repro.core import CompressionConfig, client_compress, init_states, server_aggregate
from repro.utils import tree_map, tree_zeros_like

HERE = os.path.dirname(os.path.abspath(__file__))

SCHEME_GRID = ("none", "topk", "randomk", "dgc", "gmc", "dgcwgm", "dgcwgmf")
SELECTORS = ("exact", "sampled")
WIRES = ("float32", "float16", "bfloat16")
ROUNDS = 3
CLIENTS = 2

# Extra configurations that exercise scheme knobs beyond the main grid.
# name -> (kwargs for CompressionConfig, kwargs for client_compress)
VARIANTS = {
    "dgcwgmf_fednova": (
        dict(scheme="dgcwgmf", rate=0.1, tau=0.5, fusion_weighting="fednova"),
        dict(local_steps=4.0, mean_steps=2.0),
    ),
    "dgcwgmf_warmup": (
        dict(scheme="dgcwgmf", rate=0.1, tau=0.6, tau_warmup_rounds=20),
        {},
    ),
    "dgc_global_topk": (
        dict(scheme="dgc", rate=0.1, per_tensor=False),
        {},
    ),
}


def _params_and_grads():
    """Inputs drawn with numpy, so they do not change with JAX's PRNG
    implementation (``jax_threefry_partitionable`` flipped in JAX 0.5)."""
    params = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((128,))}
    rng = np.random.default_rng(1234)
    grads = [
        [{"w": jnp.asarray(rng.standard_normal((64, 32), dtype=np.float32)),
          "b": jnp.asarray(rng.standard_normal((128,), dtype=np.float32))}
         for _ in range(CLIENTS)]
        for _ in range(ROUNDS)
    ]
    return params, grads


def run_config(tag: str, cfg: CompressionConfig, out: dict, compress_kw=None):
    compress_kw = compress_kw or {}
    params, grads = _params_and_grads()
    cstates = [init_states(cfg, params)[0] for _ in range(CLIENTS)]
    _, sstate = init_states(cfg, params)
    gbar = tree_zeros_like(params)
    for t in range(ROUNDS):
        g_sum = tree_zeros_like(params)
        for c in range(CLIENTS):
            G, cstates[c], info = client_compress(
                cfg, cstates[c], grads[t][c], gbar, t, **compress_kw)
            g_sum = tree_map(jnp.add, g_sum, G)
            if c == 0:
                for k in G:
                    out[f"{tag}/r{t}/G/{k}"] = np.asarray(G[k])
                for field in ("u", "v", "m"):
                    st = getattr(cstates[c], field)
                    if st:
                        for k in st:
                            out[f"{tag}/r{t}/{field}/{k}"] = np.asarray(st[k])
                out[f"{tag}/r{t}/upload_nnz"] = np.asarray(info.upload_nnz)
        gbar, sstate, ainfo = server_aggregate(cfg, sstate, g_sum, float(CLIENTS))
        for k in gbar:
            out[f"{tag}/r{t}/bcast/{k}"] = np.asarray(gbar[k])
        out[f"{tag}/r{t}/download_nnz"] = np.asarray(ainfo.download_nnz)


def capture_schemes(path: str):
    out: dict = {}
    for scheme in SCHEME_GRID:
        for selector in SELECTORS:
            for wire in WIRES:
                tag = f"{scheme}/{selector}/{wire}"
                cfg = CompressionConfig(
                    scheme=scheme, rate=0.1, tau=0.4, selector=selector,
                    wire_dtype=wire)
                run_config(tag, cfg, out)
    for name, (cfg_kw, call_kw) in VARIANTS.items():
        run_config(f"variant/{name}", CompressionConfig(**cfg_kw), out, call_kw)
    np.savez_compressed(path, **out)
    print(f"wrote {path}: {len(out)} arrays")


def capture_fetchsgd(path: str):
    try:
        from repro.fl.fetchsgd import FetchSGDConfig, FetchSGDSimulator
    except ImportError:
        print(f"FetchSGDSimulator not available (post-refactor tree); "
              f"keeping existing {path}")
        return
    from repro.fl import FLConfig
    from tiny_task import GoldenTask

    task = GoldenTask(seed=0)
    fl = FLConfig(num_clients=4, rounds=6, batch_size=12, learning_rate=0.1,
                  eval_every=2, seed=0)
    fs = FetchSGDConfig(rows=3, cols=128, k_frac=0.05, momentum=0.9)
    sim = FetchSGDSimulator(fl, fs, task.init_fn, task.loss_fn, task.eval_fn)
    sim.run(task.batch_provider())
    out = {
        "upload_bytes": np.asarray(sim.ledger.upload_bytes),
        "download_bytes": np.asarray(sim.ledger.download_bytes),
        "rounds": np.asarray(sim.ledger.rounds),
        "k": np.asarray(sim.k),
        "final_accuracy": np.asarray(sim.final_accuracy()),
        "params/w": np.asarray(sim.params["w"]),
        "params/b": np.asarray(sim.params["b"]),
        "comm_gb_per_round": np.asarray([r["comm_gb"] for r in sim.history]),
    }
    np.savez_compressed(path, **out)
    print(f"wrote {path}: upload={sim.ledger.upload_bytes} "
          f"download={sim.ledger.download_bytes} k={sim.k} "
          f"acc={sim.final_accuracy()}")


if __name__ == "__main__":
    capture_schemes(os.path.join(HERE, "schemes_golden.npz"))
    capture_fetchsgd(os.path.join(HERE, "fetchsgd_golden.npz"))
