#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, in
one process on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 11,12,... \\
        --fault-seeds 11,12,13

For every seed: the system's first rounds as a run of the cell makes them,
against the plain float32 reference at the matmul precision the
configuration states (the program's readings: the lower end). For every
fault seed also, each against the same reference: the reference computed
in bfloat16 (the precision control), the reference on the first half of
each client's batch (the half-batch fault), and the reference at matmul
precision "highest" (a witness: how far the stated precision alone moves
each number from full float32). One JSON line per reading, with the three
leaves of the largest norm gaps; the benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.chip import harness  # noqa: E402


def readings(cell, seed: int, faults: bool):
    """Yield (variant, numbers) for one seed."""
    sim, batches, counts, prog, rounds = harness.start(cell, seed)
    del sim, batches, counts
    harness._free()
    ref = harness.reference_trajectory(cell, seed, rounds)
    yield "program", prog, ref
    if faults:
        yield "control_bf16", harness.reference_trajectory(
            cell, seed, rounds, dtype=jnp.bfloat16), ref
        yield "fault_half_batch", harness.reference_trajectory(
            cell, seed, rounds, batch_share=0.5), ref
        yield "witness_highest", harness.reference_trajectory(
            cell, seed, rounds, precision="highest"), ref


def worst_leaves(got, ref, top: int = 3) -> dict:
    """The leaves of the largest norm gaps, per number, with both norms."""
    out = {}
    norms = lambda leaves: [harness._norm(x) for x in leaves]
    delta = lambda t: [a - b for a, b in zip(t.theta, t.theta0, strict=True)]
    pairs = {"bcast1": (norms(got.bcasts[0]), norms(ref.bcasts[0])),
             "delta": (norms(delta(got)), norms(delta(ref)))}
    for f in ref.state_norms:
        pairs[f"state_{f}"] = (got.state_norms[f], ref.state_norms[f])
    for name, (g, r) in pairs.items():
        g, r = np.asarray(g), np.asarray(r)
        gap = np.abs(g - r) / np.maximum(r, np.median(r))
        out[name] = [[ref.paths[i], float(gap[i]), float(g[i]), float(r[i])]
                     for i in np.argsort(-gap)[:top]]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--fault-seeds", default="", help="comma-separated")
    args = ap.parse_args(argv)
    cell = harness.load_cell(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    device = harness.device_info(cell.chips, require_tpu=True)
    faults = {int(s) for s in args.fault_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        for variant, got, ref in readings(cell, seed, seed in faults):
            print(json.dumps({"workload": cell.name, "seed": seed, "variant": variant,
                              **harness.compare(got, ref),
                              "seconds": time.perf_counter() - t0,
                              "device": device["kind"], "worst": worst_leaves(got, ref)}),
                  flush=True)
    print(f"calibrate: {time.perf_counter() - T_START} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
