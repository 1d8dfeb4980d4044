import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
# (No `from __future__ import annotations` here for the same reason: the
# XLA_FLAGS assignment must be the first statements in the file.)

"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) combination:
  jit(step).lower(*ShapeDtypeStructs).compile()
on the production meshes — (16,16)=256 chips single-pod and
(2,16,16)=512 chips two-pod — recording memory_analysis(),
cost_analysis() and the per-chip collective bytes parsed from the
SPMD-partitioned HLO. No arrays are ever allocated at full scale.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all --grad-sync paper  # GMF on

``--topology ring|hierarchical`` lowers a TopologyEngine round instead
(repro.topo): the smoke-scale cohort laid over a faked client mesh with
the shard leaf backend, recording the wire graph's partitioned-HLO
collective profile (the hop loop / tier re-compression are what change
the collective mix vs the star engines):

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \\
      --topology ring --out /tmp/dryrun

Artifacts: experiments/dryrun/<arch>__<shape>__<mesh>[__<sync>].json
(topology runs: <arch>__topo_<topology>__clients<N>.json)
"""


import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as configs
from repro.analysis.jaxpr_audit import parse_collective_bytes
from repro.configs.base import INPUT_SHAPES, TrainConfig
from repro.core import CompressionConfig
from repro.dist import sharding as shr
from repro.dist import step as dstep
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.utils import tree_map

# v5e hardware constants (roofline denominators).
PEAK_FLOPS = 197e12         # bf16 FLOP/s per chip
HBM_BW = 819e9              # bytes/s per chip
ICI_BW = 50e9               # bytes/s per link

# parse_collective_bytes lives in repro.analysis.jaxpr_audit (imported
# above): the one-off inspection here and the standing CI collective gate
# must count HLO collectives the same way.


def _sds(tree):
    return tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def input_specs(cfg, shape, *, mode: str):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    B, T = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if mode in ("train", "prefill"):
        if cfg.family == "audio":
            toks = jax.ShapeDtypeStruct((B, cfg.num_codebooks, T), i32)
            batch = {"tokens": toks}
            if mode == "train":
                batch["labels"] = jax.ShapeDtypeStruct((B, cfg.num_codebooks, T), i32)
            return batch
        if cfg.family == "vlm":
            p = cfg.num_patches
            t_text = T - p
            batch = {
                "tokens": jax.ShapeDtypeStruct((B, t_text), i32),
                "patch_embeds": jax.ShapeDtypeStruct((B, p, cfg.d_model), jnp.dtype(cfg.dtype)),
            }
            if mode == "train":
                batch["labels"] = jax.ShapeDtypeStruct((B, T), i32)
            return batch
        batch = {"tokens": jax.ShapeDtypeStruct((B, T), i32)}
        if mode == "train":
            batch["labels"] = jax.ShapeDtypeStruct((B, T), i32)
        return batch
    if mode == "decode":
        if cfg.family == "audio":
            return {"tokens": jax.ShapeDtypeStruct((B, cfg.num_codebooks), i32)}
        return {"tokens": jax.ShapeDtypeStruct((B,), i32)}
    raise ValueError(mode)


def _shardings(mesh, specs):
    return tree_map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )


def lower_one(arch_id: str, shape_name: str, *, multi_pod: bool, grad_sync: str,
              wire_dtype: str = "float32", downlink: str = "none"):
    """Lower+compile one combination; returns (record, compiled)."""
    shape = INPUT_SHAPES[shape_name]
    cfg = configs.get_config(arch_id)
    if shape_name == "long_500k":
        cfg = configs.get_long_variant(arch_id)
        if cfg is None:
            return {"status": "skipped",
                    "reason": "full attention; sub-quadratic variant not defined "
                              "(DESIGN.md §5)"}, None
    mesh = make_production_mesh(multi_pod=multi_pod)
    key = jax.random.PRNGKey(0)

    params_sds = jax.eval_shape(lambda: transformer.init_params(cfg, key))
    fsdp = dstep.needs_fsdp(cfg)
    pspecs = shr.param_specs(params_sds, fsdp=fsdp, mesh=mesh)
    p_shard = _shardings(mesh, pspecs)

    t0 = time.time()
    if shape.mode == "train":
        if grad_sync == "paper":
            sync = configs.default_grad_sync(cfg, multi_pod=multi_pod)
        else:
            sync = grad_sync
        tcfg = TrainConfig(learning_rate=1e-2, total_steps=1000, grad_sync=sync)
        ccfg = CompressionConfig(
            scheme="dgcwgmf", rate=0.1, tau=0.3,
            selector="sampled",  # exact top-k on 10^9-element tensors is a
                                 # compile-time/comms hazard; DGC's sampled
                                 # estimator is the production selector
            wire_dtype=wire_dtype,
            downlink_stage=None if downlink == "none" else downlink,
        )
        state_sds = jax.eval_shape(
            lambda p: dstep.init_train_state(cfg, tcfg, ccfg, p, mesh), params_sds
        )
        st_specs = dstep.train_state_specs(cfg, tcfg, ccfg, params_sds, mesh)
        st_shard = _shardings(mesh, st_specs)
        batch_sds = input_specs(cfg, shape, mode="train")
        b_shard = _shardings(mesh, shr.train_batch_specs(cfg, mesh))
        step_fn = dstep.make_train_step(cfg, tcfg, ccfg, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step_fn, in_shardings=(st_shard, b_shard), donate_argnums=(0,)
            ).lower(state_sds, batch_sds)
        extra = {"grad_sync": sync, "scheme": "dgcwgmf", "downlink": downlink}
    elif shape.mode == "prefill":
        batch_sds = input_specs(cfg, shape, mode="prefill")
        b_shard = _shardings(
            mesh,
            {k: v for k, v in shr.train_batch_specs(cfg, mesh).items() if k in batch_sds},
        )
        step_fn = dstep.make_prefill_step(cfg, mesh, cache_len=shape.seq_len)
        # The emitted KV cache must leave the step sharded (it is the big
        # serving state) — without this, XLA materialises it replicated.
        cache_sds = jax.eval_shape(
            lambda: transformer.init_cache(cfg, shape.global_batch, shape.seq_len)
        )
        c_shard = _shardings(mesh, shr.cache_specs_from(cache_sds, mesh))
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step_fn, in_shardings=(p_shard, b_shard), out_shardings=(None, c_shard)
            ).lower(params_sds, batch_sds)
        extra = {}
    else:  # decode
        cache_sds = jax.eval_shape(
            lambda: transformer.init_cache(cfg, shape.global_batch, shape.seq_len)
        )
        c_shard = _shardings(mesh, shr.cache_specs_from(cache_sds, mesh))
        tok_sds = input_specs(cfg, shape, mode="decode")["tokens"]
        tok_shard = _shardings(
            mesh, shr.decode_batch_specs(cfg, mesh, shape.global_batch)["tokens"]
        )
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        step_fn = dstep.make_serve_step(cfg, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step_fn,
                in_shardings=(p_shard, c_shard, tok_shard, None),
                donate_argnums=(1,),
            ).lower(params_sds, cache_sds, tok_sds, pos_sds)
        extra = {}
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = parse_collective_bytes(compiled.as_text())
    chips = mesh.devices.size

    flops_per_chip = float(cost.get("flops", 0.0))
    bytes_per_chip = float(cost.get("bytes accessed", 0.0))
    record = {
        "status": "ok",
        "arch": arch_id,
        "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "chips": chips,
        "mode": shape.mode,
        **extra,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_chip": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes_per_chip": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes_per_chip": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes_per_chip": (
                (getattr(mem, "argument_size_in_bytes", 0) or 0)
                + (getattr(mem, "temp_size_in_bytes", 0) or 0)
            ),
        },
        "cost": {
            "flops_per_chip": flops_per_chip,
            "hbm_bytes_per_chip": bytes_per_chip,
        },
        "collectives": coll,
        "roofline_terms_s": {
            "compute": flops_per_chip / PEAK_FLOPS,
            "memory": bytes_per_chip / HBM_BW,
            "collective": coll["total_bytes"] / ICI_BW,
        },
        "model": {
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
        },
    }
    terms = record["roofline_terms_s"]
    record["dominant_term"] = max(terms, key=terms.get)
    return record, compiled


def lower_topology(arch_id: str, topology: str, *, clients: int = 8,
                   ring_hops: int = 1, groups: int = 2, batch: int = 2,
                   seq_len: int = 128):
    """Lower+compile one TopologyEngine round (repro.topo): the smoke-scale
    LM with the cohort laid over a faked client mesh (shard leaf backend).

    Unlike :func:`lower_one` this allocates real (smoke-scale) client
    state — the FL engines close over concrete state pytrees — which is
    fine: the artifact of interest is the partitioned-HLO collective
    profile of the ring hop loop / hierarchical tier re-compression, not
    full-scale memory numbers.
    """
    import numpy as np

    from repro.fl import FLConfig, FLSimulator, LMTask

    cfg = configs.get_smoke(arch_id)
    fl = FLConfig(
        num_clients=clients, rounds=1, batch_size=batch,
        backend="shard", shards=clients, topology=topology,
        ring_hops=ring_hops if topology == "ring" else 0,
        groups=groups if topology == "hierarchical" else 1,
    )
    ccfg = CompressionConfig(scheme="dgcwgmf", rate=0.1, tau=0.3,
                             selector="sampled")
    task = LMTask(cfg, num_clients=clients, batch_size=batch,
                  seq_len=seq_len)
    sim = FLSimulator(fl, ccfg, task.init_fn, task.loss_fn)
    eng = sim.engine
    batches = task.batch_provider(0, np.arange(clients),
                                  np.random.default_rng(0))
    idx = jnp.arange(clients)
    t = jnp.asarray(0)
    lr = jnp.asarray(0.1, jnp.float32)
    tau = jnp.asarray(ccfg.tau, jnp.float32)

    t0 = time.time()
    if topology == "hierarchical":
        tier = eng._init_tier_states(sim.params)
        lowered = eng.round_fn.lower(
            sim.params, sim.cstates, tier, sim.sstate, sim.gbar_prev,
            idx, batches, t, lr, tau)
    else:
        lowered = eng.round_fn.lower(
            sim.params, sim.cstates, sim.sstate, sim.gbar_prev,
            idx, batches, t, lr, tau)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = parse_collective_bytes(compiled.as_text())
    flops_per_chip = float(cost.get("flops", 0.0))
    bytes_per_chip = float(cost.get("bytes accessed", 0.0))
    record = {
        "status": "ok",
        "arch": arch_id,
        "mesh": f"clients{clients}",
        "chips": clients,
        "mode": "fl_round",
        "topology": topology,
        "scheme": "dgcwgmf",
        "ring_hops": ring_hops if topology == "ring" else 0,
        "groups": groups if topology == "hierarchical" else 1,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_chip": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes_per_chip": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes_per_chip": getattr(mem, "temp_size_in_bytes", None),
        },
        "cost": {
            "flops_per_chip": flops_per_chip,
            "hbm_bytes_per_chip": bytes_per_chip,
        },
        "collectives": coll,
        "model": {"params": cfg.param_count()},
    }
    return record, compiled


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(configs.ARCH_IDS))
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true", help="run every (arch × shape)")
    ap.add_argument("--grad-sync", default="paper",
                    choices=["paper", "dense", "gmf_data", "gmf_pod"],
                    help="'paper' = per-arch default (GMF where it fits)")
    ap.add_argument("--wire-dtype", default="float32",
                    choices=["float32", "bfloat16", "float16"],
                    help="sync payload dtype (bfloat16 = quantisation-aware EF)")
    ap.add_argument("--downlink", default="none", choices=["none", "topk"],
                    help="downlink stage for train shapes (topk = compressed "
                         "broadcast with sharded server residual)")
    ap.add_argument("--topology", default="none",
                    choices=["none", "ring", "hierarchical"],
                    help="lower a TopologyEngine FL round (repro.topo) on a "
                         "faked client mesh instead of the dist step sweep")
    ap.add_argument("--clients", type=int, default=8,
                    help="topology runs: cohort size = client mesh size")
    ap.add_argument("--ring-hops", type=int, default=1,
                    help="topology ring: handoffs per segment")
    ap.add_argument("--groups", type=int, default=2,
                    help="topology hierarchical: edge aggregator count")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = list(configs.ARCH_IDS) if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    if args.topology != "none":
        for arch in archs:
            tag = f"{arch}__topo_{args.topology}__clients{args.clients}"
            print(f"=== {tag}", flush=True)
            try:
                record, compiled = lower_topology(
                    arch, args.topology, clients=args.clients,
                    ring_hops=args.ring_hops, groups=args.groups)
            except Exception as e:
                failures += 1
                record = {
                    "status": "failed",
                    "arch": arch,
                    "topology": args.topology,
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:],
                }
                print(f"    FAILED: {record['error'][:300]}", flush=True)
            else:
                c = record["collectives"]
                print(f"    ok  compile={record['compile_s']}s "
                      f"collectives={c['num_collectives']} "
                      f"coll_bytes/chip={c['total_bytes']/1e6:.2f}MB",
                      flush=True)
                del compiled
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(record, f, indent=2)
        print(f"done; {failures} failures")
        return 1 if failures else 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                mesh_name = "pod2x16x16" if multi else "pod16x16"
                tag = f"{arch}__{shape}__{mesh_name}"
                if args.grad_sync != "paper" and INPUT_SHAPES[shape].mode == "train":
                    tag += f"__{args.grad_sync}"
                if args.wire_dtype != "float32" and INPUT_SHAPES[shape].mode == "train":
                    tag += "__wire16"
                if args.downlink != "none" and INPUT_SHAPES[shape].mode == "train":
                    tag += f"__dl_{args.downlink}"
                path = os.path.join(args.out, tag + ".json")
                print(f"=== {tag}", flush=True)
                try:
                    record, compiled = lower_one(
                        arch, shape, multi_pod=multi, grad_sync=args.grad_sync,
                        wire_dtype=args.wire_dtype, downlink=args.downlink,
                    )
                except Exception as e:  # a failure here is a bug in the system
                    failures += 1
                    record = {
                        "status": "failed",
                        "arch": arch,
                        "shape": shape,
                        "mesh": mesh_name,
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-4000:],
                    }
                    print(f"    FAILED: {record['error'][:300]}", flush=True)
                else:
                    if record["status"] == "ok":
                        t = record["roofline_terms_s"]
                        print(
                            f"    ok  compile={record['compile_s']}s "
                            f"peak/chip={record['memory']['peak_bytes_per_chip']/1e9:.2f}GB "
                            f"compute={t['compute']*1e3:.2f}ms mem={t['memory']*1e3:.2f}ms "
                            f"coll={t['collective']*1e3:.2f}ms dom={record['dominant_term']}",
                            flush=True,
                        )
                    else:
                        print(f"    skipped: {record['reason']}", flush=True)
                    del compiled
                with open(path, "w") as f:
                    json.dump(record, f, indent=2)
    print(f"done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
