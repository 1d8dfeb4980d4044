"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train \
        --arch llama3.2-1b --smoke --steps 50 --grad-sync gmf_data \
        --scheme dgcwgmf --rate 0.1 --tau 0.3

On this container it runs the smoke-scale configs on the local device mesh;
on a real v5e deployment the same entrypoint runs the full configs on the
production mesh (set --mesh-shape / --multi-pod; jax.distributed handles
process bootstrap). Per-step metrics include the exact compressed-sync
traffic (upload nnz per shard, broadcast union nnz).

``--backend async`` trains the same LM through the asynchronous buffered
FL engine instead of the SPMD dist step: ``--clients`` simulated clients
with sampled delays/dropout (``--delay-model``/``--delay-mean``/
``--dropout``), a ``--buffer-size``-payload server buffer, and a
``--staleness`` weighting policy (try ``--scheme async_dgcwgmf``):

    PYTHONPATH=src python -m repro.launch.train \
        --arch llama3.2-1b --smoke --steps 12 --backend async \
        --scheme async_dgcwgmf --buffer-size 2 --delay-model geometric \
        --delay-mean 1.0

``--backend fl`` runs the synchronous FL round engines and exposes the
wire-graph topology axis (repro.topo): ``--topology ring`` threads each
compensated delta through ``--ring-hops`` neighbours with a periodic
server sync every ``--sync-every`` rounds; ``--topology hierarchical``
aggregates ``--groups`` leaf groups at edge aggregators that re-compress
upward with their own ``--tier-scheme``/``--tier-rate``. A non-star
``--topology`` implies ``--backend fl``:

    PYTHONPATH=src python -m repro.launch.train \
        --arch llama3.2-1b --smoke --steps 12 --topology hierarchical \
        --groups 2 --tier-scheme dgcwgmf --clients 8 --batch 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
import repro.obs as obs
from repro.checkpoint import save as save_ckpt
from repro.configs.base import TrainConfig
from repro.core import SCHEMES, CompressionConfig, resolve
from repro.core.stages import get_stage
from repro.data.pipeline import SyntheticLMStream
from repro.dist import sharding as shr
from repro.dist import step as dstep
from repro.launch.mesh import make_mesh
from repro.models import transformer
from repro.topo import TOPOLOGIES
from repro.utils import tree_size
from repro.utils.compile_cache import enable_compile_cache


def parse_stage_overrides(spec: str) -> dict:
    """``selector=randomk,fusion=none`` -> CompressionConfig override kwargs.

    Keys are stage kinds; values must be registered stage names (list them
    with ``python -m repro.core.registry``).
    """
    field_of = {"selector": "selector_stage", "compensator": "compensator_stage",
                "fusion": "fusion_stage", "wire": "wire_stage",
                "rotation": "rotation_stage",
                "downlink": "downlink_stage", "staleness": "staleness_stage",
                "rate_control": "rate_control_stage"}
    out = {}
    for part in filter(None, (p.strip() for p in spec.split(","))):
        if "=" not in part:
            raise SystemExit(f"--stage entries are kind=name, got {part!r}")
        kind, name = (s.strip() for s in part.split("=", 1))
        if kind not in field_of:
            raise SystemExit(
                f"unknown stage kind {kind!r}; choose from {tuple(field_of)}")
        try:
            get_stage(kind, name)
        except ValueError as e:
            raise SystemExit(str(e)) from None
        out[field_of[kind]] = name
    return out


def build_mesh(args):
    n = jax.device_count()
    if args.mesh_shape:
        shape = tuple(int(x) for x in args.mesh_shape.split(","))
        axes = ("pod", "data", "model")[-len(shape):]
        return make_mesh(shape, axes)
    if n == 1:
        return make_mesh((1, 1), ("data", "model"))
    model = 2 if n % 2 == 0 else 1  # (n, 1) on odd device counts
    return make_mesh((n // model, model), ("data", "model"))


def run_async(args, ccfg, cfg):
    """LM pretraining through the asynchronous buffered FL engine
    (``FLConfig.backend="async"``): K simulated clients with sampled
    delays/dropout, buffered staleness-weighted aggregation. Same
    loss-improvement exit code as the dist path, so CI can gate on it."""
    from repro.fl import FLConfig, FLSimulator, LMTask

    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"async: clients={args.clients} cohort={args.cohort or args.clients} "
          f"buffer={args.buffer_size or args.cohort or args.clients} "
          f"delay={args.delay_model}(mean={args.delay_mean}) "
          f"dropout={args.dropout}")
    fl = FLConfig(
        num_clients=args.clients, rounds=args.steps,
        clients_per_round=args.cohort, batch_size=args.batch,
        learning_rate=args.lr, seed=args.seed, backend="async",
        buffer_size=args.buffer_size, delay_model=args.delay_model,
        delay_mean=args.delay_mean, delay_max=args.delay_max,
        dropout_rate=args.dropout,
    )
    task = LMTask(cfg, num_clients=args.clients, batch_size=args.batch,
                  seq_len=args.seq_len)
    sim = FLSimulator(fl, ccfg, task.init_fn, task.loss_fn)
    history = []
    t_start = time.time()

    def on_round(t, s):
        rec = dict(s.history[-1])
        rec["loss"] = task.held_out_loss(s.params)
        history.append(rec)
        if t % args.log_every == 0 or t == args.steps - 1:
            print(f"[{t:5d}] loss={rec['loss']:.4f} "
                  f"applies={rec['applies']} pending={rec['pending']} "
                  f"in_flight={rec['in_flight']} "
                  f"comm={rec['comm_gb']:.4f}GB", flush=True)

    sim.run(task.batch_provider, on_round=on_round)
    dt = time.time() - t_start
    print(f"{args.steps} ticks in {dt:.1f}s ({dt/args.steps*1e3:.0f} ms/tick)")
    print("ledger:", json.dumps(sim.ledger.summary()))
    obs.get().event("summary", ticks=args.steps, wall_s=dt,
                    **sim.ledger.summary())
    if args.checkpoint:
        save_ckpt(args.checkpoint, jax.device_get(sim.params), step=args.steps)
        print(f"checkpoint -> {args.checkpoint}.npz")
    if args.metrics_out:
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=2)
    first = np.mean([h["loss"] for h in history[:3]])
    last = np.mean([h["loss"] for h in history[-3:]])
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    return 0 if last < first else 2


def run_fl(args, ccfg, cfg):
    """LM pretraining through the synchronous FL round engines
    (``--fl-backend vmap|shard``) with the wire-graph topology axis
    (``--topology star|ring|hierarchical``, repro.topo). Same
    loss-improvement exit code as the dist path, so CI can gate on it."""
    from repro.fl import FLConfig, FLSimulator, LMTask

    topo_s = ""
    if args.topology == "ring":
        topo_s = f" hops={args.ring_hops} sync_every={args.sync_every}"
    elif args.topology == "hierarchical":
        topo_s = (f" groups={args.groups} "
                  f"tier={args.tier_scheme or '<preset>'}"
                  f"@{args.tier_rate} sync_every={args.sync_every}")
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"fl: topology={args.topology}{topo_s} clients={args.clients} "
          f"cohort={args.cohort or args.clients} "
          f"leaf_backend={args.fl_backend}")
    fl = FLConfig(
        num_clients=args.clients, rounds=args.steps,
        clients_per_round=args.cohort, batch_size=args.batch,
        learning_rate=args.lr, seed=args.seed,
        backend=args.fl_backend, shards=args.shards,
        topology=args.topology, ring_hops=args.ring_hops,
        sync_every=args.sync_every, groups=args.groups,
    )
    task = LMTask(cfg, num_clients=args.clients, batch_size=args.batch,
                  seq_len=args.seq_len)
    sim = FLSimulator(fl, ccfg, task.init_fn, task.loss_fn)
    history = []
    t_start = time.time()

    def on_round(t, s):
        rec = dict(s.history[-1])
        rec["loss"] = task.held_out_loss(s.params)
        history.append(rec)
        if t % args.log_every == 0 or t == args.steps - 1:
            if "server_ingress_gb" in rec:
                print(f"[{t:5d}] loss={rec['loss']:.4f} "
                      f"ingress={rec['server_ingress_gb']:.4f}GB "
                      f"peer={rec['peer_gb']:.4f}GB "
                      f"total={rec['comm_gb']:.4f}GB"
                      f"{' sync' if rec.get('synced') else ''}", flush=True)
            else:
                print(f"[{t:5d}] loss={rec['loss']:.4f} "
                      f"comm={rec['comm_gb']:.4f}GB", flush=True)

    sim.run(task.batch_provider, on_round=on_round)
    dt = time.time() - t_start
    print(f"{args.steps} rounds in {dt:.1f}s ({dt/args.steps*1e3:.0f} ms/round)")
    print("ledger:", json.dumps(sim.ledger.summary()))
    obs.get().event("summary", wall_s=dt, topology=args.topology,
                    **sim.ledger.summary())
    if args.checkpoint:
        save_ckpt(args.checkpoint, jax.device_get(sim.params), step=args.steps)
        print(f"checkpoint -> {args.checkpoint}.npz")
    if args.metrics_out:
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=2)
    first = np.mean([h["loss"] for h in history[:3]])
    last = np.mean([h["loss"] for h in history[-3:]])
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    return 0 if last < first else 2


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--backend", default="dist",
                    choices=["dist", "async", "fl"],
                    help="dist = SPMD mesh trainer (repro.dist); async = "
                         "asynchronous buffered FL engine (fl/engine.py); "
                         "fl = synchronous FL round engines with the "
                         "--topology axis (a non-star --topology implies fl)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-sync", default="gmf_data",
                    choices=["dense", "gmf_data", "gmf_pod"])
    ap.add_argument("--scheme", default="dgcwgmf", choices=list(SCHEMES),
                    help="compression preset (full registry incl. fetchsgd; "
                         "list with `python -m repro.core.registry`)")
    ap.add_argument("--stage", default="",
                    help="override preset stages, e.g. "
                         "'selector=randomk,fusion=none,wire=float16,"
                         "rotation=hadamard,downlink=topk,"
                         "rate_control=adaptive'")
    ap.add_argument("--rate-controller", default=None,
                    choices=["fixed", "adaptive"],
                    help="override the preset's per-client rate controller "
                         "(adaptive modulates each sampled client's "
                         "effective rate from its EF-residual mass, "
                         "bandwidth budget and staleness gap; try "
                         "--scheme adaptive_dgcwgmf)")
    ap.add_argument("--rate", type=float, default=0.1)
    ap.add_argument("--tau", type=float, default=0.3)
    ap.add_argument("--downlink-rate", type=float, default=0.1,
                    help="topk downlink: fraction of the broadcast kept per "
                         "step (dropped entries error-feed through the "
                         "server residual)")
    ap.add_argument("--sketch-cols", type=int, default=10_000,
                    help="fetchsgd: count-sketch columns (upload size = rows*cols)")
    ap.add_argument("--sketch-k-frac", type=float, default=0.01,
                    help="fetchsgd: heavy-hitter fraction per round")
    ap.add_argument("--wire-dtype", default="float32",
                    choices=["float32", "float16", "bfloat16"],
                    help="sync payload dtype (16-bit = quantisation-aware EF)")
    # async backend (asynchronous buffered FL engine) knobs
    ap.add_argument("--clients", type=int, default=8,
                    help="async: number of simulated clients")
    ap.add_argument("--cohort", type=int, default=0,
                    help="async: clients dispatched per tick (0 = all)")
    ap.add_argument("--buffer-size", type=int, default=0,
                    help="async: server flushes after this many payloads "
                         "arrive (0 = cohort size, the synchronous limit)")
    ap.add_argument("--staleness", default=None,
                    choices=["none", "poly", "gmf_damp"],
                    help="async: override the preset's staleness weighting "
                         "stage (try --scheme async_dgcwgmf)")
    ap.add_argument("--delay-model", default="none",
                    choices=["none", "uniform", "geometric", "lognormal"],
                    help="async: per-payload network delay distribution")
    ap.add_argument("--delay-mean", type=float, default=0.0,
                    help="async: mean delay in server ticks")
    ap.add_argument("--delay-max", type=int, default=0,
                    help="async: clip every delay draw (0 = uncapped)")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="async: per-payload probability the upload is lost")
    # fl backend (synchronous round engines + wire-graph topology) knobs
    ap.add_argument("--topology", default="star", choices=list(TOPOLOGIES),
                    help="fl: wire graph (repro.topo) — star = hub-and-spoke, "
                         "ring = segmented client-to-client passing, "
                         "hierarchical = two-tier edge aggregation")
    ap.add_argument("--ring-hops", type=int, default=0,
                    help="ring: payload handoffs per segment (cohort must "
                         "divide into segments of hops+1; 0 = star-identical)")
    ap.add_argument("--sync-every", type=int, default=1,
                    help="ring/hierarchical: broadcast reaches clients every "
                         "N rounds (RingFed periodic sync)")
    ap.add_argument("--groups", type=int, default=1,
                    help="hierarchical: number of edge aggregators "
                         "(cohort must divide evenly; 1 = star-identical "
                         "with the dense tier passthrough)")
    ap.add_argument("--tier-scheme", default=None,
                    help="hierarchical: aggregator-tier re-compression "
                         "preset (any non-sketch scheme; default = the leaf "
                         "preset's tier slot, dense passthrough)")
    ap.add_argument("--tier-rate", type=float, default=0.1,
                    help="hierarchical: selector rate for the tier scheme")
    ap.add_argument("--fl-backend", default="vmap",
                    choices=["vmap", "shard"],
                    help="fl: leaf round-engine backend (shard lays the "
                         "cohort over a client device mesh)")
    ap.add_argument("--shards", type=int, default=0,
                    help="fl: shard backend mesh size (0 = all devices)")
    ap.add_argument("--mesh-shape", default=None, help="e.g. 2,16,16")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--obs", action="store_true",
                    help="enable the repro.obs telemetry spine (JSONL events "
                         "+ metrics.prom/summary.json under --obs-dir)")
    ap.add_argument("--obs-dir", default="runs/obs",
                    help="telemetry output directory (with --obs)")
    args = ap.parse_args()

    if args.topology != "star":
        if args.backend == "async":
            raise SystemExit("--topology ring/hierarchical needs the "
                             "synchronous FL engines (--backend fl)")
        if args.backend == "dist":
            args.backend = "fl"  # a non-star topology implies the FL engines
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    overrides = parse_stage_overrides(args.stage)
    if args.staleness is not None:
        overrides["staleness_stage"] = args.staleness
    if args.rate_controller is not None:
        overrides["rate_control_stage"] = args.rate_controller
    ccfg = CompressionConfig(scheme=args.scheme, rate=args.rate, tau=args.tau,
                             wire_dtype=args.wire_dtype,
                             downlink_rate=args.downlink_rate,
                             sketch_cols=args.sketch_cols,
                             sketch_k_frac=args.sketch_k_frac,
                             tier_scheme=args.tier_scheme,
                             tier_rate=args.tier_rate,
                             **overrides)
    scheme = resolve(ccfg)
    print(f"scheme={scheme.name}: selector={scheme.selector.name} "
          f"compensator={scheme.compensator.name} fusion={scheme.fusion.name} "
          f"wire={scheme.wire.name} rotation={scheme.rotation.name} "
          f"downlink={scheme.downlink.name} "
          f"staleness={scheme.staleness.name} "
          f"rate_control={scheme.rate_control.name}")
    if args.obs:
        obs.configure(args.obs_dir)
        obs.get().event("run_start", run=f"train-{args.arch}",
                        argv=sys.argv[1:], backend=args.backend,
                        scheme=args.scheme, rate=args.rate, steps=args.steps,
                        topology=args.topology)
    try:
        if args.backend == "async":
            return run_async(args, ccfg, cfg)
        if args.backend == "fl":
            return run_fl(args, ccfg, cfg)
        return run_dist(args, ccfg, cfg, scheme)
    finally:
        if args.obs:
            obs.export.write_all(args.obs_dir)
            obs.shutdown()
            print(f"obs -> {args.obs_dir}/events.jsonl")


def run_dist(args, ccfg, cfg, scheme):
    mesh = build_mesh(args)
    if args.grad_sync == "gmf_pod" and "pod" not in mesh.axis_names:
        raise SystemExit("--grad-sync gmf_pod needs a pod axis (--mesh-shape 2,x,y)")
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M mesh={dict(zip(mesh.axis_names, mesh.devices.shape, strict=True))}")

    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       grad_sync=args.grad_sync, lr_schedule="cosine",
                       warmup_steps=max(1, args.steps // 20))

    key = jax.random.PRNGKey(args.seed)
    params = transformer.init_params(cfg, key)
    state = dstep.init_train_state(cfg, tcfg, ccfg, params, mesh)
    specs = dstep.train_state_specs(cfg, tcfg, ccfg, params, mesh)
    st_sh = shr.named_shardings(mesh, specs)
    b_sh = shr.named_shardings(mesh, shr.train_batch_specs(cfg, mesh))
    state = jax.device_put(state, st_sh)

    stream = SyntheticLMStream(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len, batch_size=args.batch,
        seed=args.seed, num_codebooks=cfg.num_codebooks,
        num_patches=cfg.num_patches, d_model=cfg.d_model,
    )
    step_fn = jax.jit(dstep.make_train_step(cfg, tcfg, ccfg, mesh), donate_argnums=(0,))
    # wire accounting comes from the scheme's wire stage (16-bit payloads at
    # 2 bytes/value; sketch uploads value-only) — dense sync ships fp32.
    if args.grad_sync == "dense":
        from repro.core import CostModel
        cost = CostModel()
    else:
        cost = scheme.cost_model()
    history = []
    # static param count for the byte accounting: the traced
    # metrics["total_params"] is a device float32 and rounds above 2^24
    total_static = float(tree_size(params))
    rec_obs = obs.get()
    compile_s = 0.0
    steady_ms = []
    t_start = time.time()
    for step, batch in zip(range(args.steps), stream, strict=False):
        t_step = time.perf_counter()
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        batch = jax.device_put(batch, {k: b_sh[k] for k in batch})
        state, metrics = step_fn(state, batch)
        # deliberate sync: float() blocks on async dispatch, so step_ms
        # below measures real compute, not enqueue time
        rec = {"step": step, "loss": float(metrics["loss"])}  # repro-noqa: REP004
        # Step 0 pays the jit compile; folding it into the per-step mean
        # makes short smoke runs look 10-100x slower than steady state, so
        # it is timed (and recorded) as its own series.
        step_ms = (time.perf_counter() - t_step) * 1e3
        if step == 0:
            compile_s = step_ms / 1e3
            rec_obs.gauge_set("train.compile_s", compile_s)
        else:
            steady_ms.append(step_ms)
            rec_obs.observe("train.step_ms", step_ms)
        rec["step_ms"] = step_ms
        up_bytes = down_bytes = up_nnz = 0.0
        if "upload_nnz" in metrics:
            total = total_static
            # per-shard nnz arrive as an exact int32 vector; mean in host f64.
            # Per-step D2H of a K-vector is the accounting product behavior
            # and lands after step_ms is measured.
            shard_nnz = np.asarray(metrics["upload_nnz"], np.float64)  # repro-noqa: REP004
            up_nnz = float(shard_nnz.mean())
            up = float(cost.upload_payload_bytes(up_nnz, total))
            down = float(cost.payload_bytes(float(metrics["download_nnz"]), total))  # repro-noqa: REP004 (scalar, post-step_ms)
            up_bytes = float(np.sum(cost.upload_payload_bytes(shard_nnz, total)))
            down_bytes = down
            rec.update(upload_mb_per_shard=up / 1e6, broadcast_mb=down / 1e6,
                       dense_mb=total * 4 / 1e6)
        history.append(rec)
        if rec_obs.enabled:
            rec_obs.event("round", round=step, wall_ms=step_ms,
                          upload_bytes=up_bytes, download_bytes=down_bytes,
                          loss=rec["loss"])
            obs.health.record_round_health(
                rec_obs, round_idx=step, cstates=state.cstate,
                sstate=state.sstate, bcast=state.gbar,
                upload_nnz_mean=up_nnz, total_params=total_static,
                target_rate=0.0 if args.grad_sync == "dense" else ccfg.rate)
        if step % args.log_every == 0 or step == args.steps - 1:
            extra = (f" up/shard={rec['upload_mb_per_shard']:.2f}MB "
                     f"bcast={rec['broadcast_mb']:.2f}MB vs dense={rec['dense_mb']:.2f}MB"
                     if "upload_mb_per_shard" in rec else "")
            print(f"[{step:5d}] loss={rec['loss']:.4f}{extra}", flush=True)

    dt = time.time() - t_start
    steady = float(np.mean(steady_ms)) if steady_ms else 0.0
    print(f"{args.steps} steps in {dt:.1f}s "
          f"(compile {compile_s:.1f}s + steady {steady:.0f} ms/step)")
    rec_obs.event("summary", steps=args.steps, wall_s=dt,
                  compile_s=compile_s, steady_step_ms_mean=steady)
    if args.checkpoint:
        save_ckpt(args.checkpoint, jax.device_get(state.params), step=args.steps)
        print(f"checkpoint -> {args.checkpoint}.npz")
    if args.metrics_out:
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=2)
    # loss must improve for the driver to declare success
    first = np.mean([h["loss"] for h in history[:3]])
    last = np.mean([h["loss"] for h in history[-3:]])
    print(f"loss {first:.4f} -> {last:.4f} ({'improved' if last < first else 'NOT improved'})")
    return 0 if last < first else 2


if __name__ == "__main__":
    raise SystemExit(main())
