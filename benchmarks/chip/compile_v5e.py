#!/usr/bin/env python3
"""Compile one cell's round program for a described TPU v5e chip, on a
machine without one, and print what the compiler says of its memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_v5e.py --workload <cell>

Nothing runs: this finds what the chip's compiler would refuse, and the
program's temp, argument and code bytes, before any chip time is spent.
"""

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import argparse  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.chip import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(ROOT, args.workload)
    t = cell.traffic
    sim = harness.build_simulator(cell, seed=0)
    chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])

    def shape(x, weak=False):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip, weak_type=weak)

    family = cell.module("families", cell.family)
    pools = jax.eval_shape(lambda: family.make_pools(cell.config, t, 0))
    k = sim.sampled_per_round
    batch = tuple(jax.ShapeDtypeStruct((k, t["batch"], *p.shape[2:]), p.dtype, sharding=chip)
                  for p in pools)
    state = jax.tree.map(shape, (sim.params, sim.cstates, sim.sstate, sim.gbar_prev))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip, weak_type=True)
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)
    ids = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=chip)
    t0 = time.perf_counter()
    compiled = sim.engine.round_fn.lower(*state, ids, batch, scalar, f32, f32).compile()
    mem = compiled.memory_analysis()
    out = {"workload": args.workload, "compile_s": time.perf_counter() - t0,
           "temp_bytes": mem.temp_size_in_bytes,
           "argument_bytes": mem.argument_size_in_bytes,
           "output_bytes": mem.output_size_in_bytes,
           "alias_bytes": mem.alias_size_in_bytes,
           "code_bytes": mem.generated_code_size_in_bytes,
           "hlo_lines": compiled.as_text().count("\n")}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
