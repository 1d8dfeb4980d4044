"""Compression-kernel microbenchmark: fused Pallas pass vs unfused jnp ops.

The Pallas kernel compiles natively on a TPU and runs in interpret mode
elsewhere, so off the chip its wall-clock measures the interpreter. Each
row names the platform, and carries the HBM bytes the pass needs: the
fused GMF pass reads (U, V, M) once and writes (G, U, V, mask) once =
7·N·4 bytes, vs the unfused chain's 13·N·4 bytes (score read V,M write Z;
mask read Z; three masked updates each read+write).

  PYTHONPATH=src python -m benchmarks.kernel_bench
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from repro.kernels import gmf_compress as gk
from repro.kernels import ref

N = 1_000_000

# bytes touched per element (fp32): fused reads u,v,m + writes g,u,v,mask
FUSED_BYTES = 7 * 4
# unfused: z=|..v..m| (r2 w1), mask (r1 w1), g=v*mask (r2 w1), u*=.. (r2 w1),
# v*=.. (r2 w1)  → 13 r/w
UNFUSED_BYTES = 13 * 4


def timeit(fn, *args, iters=5):
    fn(*args)  # warm
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6


def run(out="experiments/kernel_bench.json"):
    key = jax.random.PRNGKey(0)
    u = jax.random.normal(key, (N,))
    v = jax.random.normal(jax.random.fold_in(key, 1), (N,))
    m = jax.random.normal(jax.random.fold_in(key, 2), (N,))
    nv = 1.0 / (jnp.linalg.norm(v) + 1e-16)
    nm = 1.0 / (jnp.linalg.norm(m) + 1e-16)

    platform = jax.default_backend()
    interpret = platform != "tpu"
    fused = jax.jit(
        lambda u, v, m: gk.gmf_compress_flat(
            u, v, m, inv_norm_v=nv, inv_norm_m=nm, tau=0.3, threshold=0.01,
            interpret=interpret,
        )
    )
    unfused = jax.jit(
        lambda u, v, m: ref.gmf_compress_leaf(
            u, v, m, inv_norm_v=nv, inv_norm_m=nm, tau=0.3, threshold=0.01
        )
    )
    us_fused = timeit(fused, u, v, m)
    us_unfused = timeit(unfused, u, v, m)
    mode = "interpret" if interpret else "native"
    rows = [
        {
            "name": f"gmf_fused_pallas_{mode}",
            "us_per_call": us_fused,
            "derived": f"hbm_bytes={FUSED_BYTES * N};platform={platform}",
        },
        {
            "name": "gmf_unfused_jnp",
            "us_per_call": us_unfused,
            "derived": f"hbm_bytes={UNFUSED_BYTES * N};platform={platform}",
        },
    ]
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=2)
    return rows


if __name__ == "__main__":
    for r in run():
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}", flush=True)
