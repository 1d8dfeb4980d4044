"""Serving driver: fixed-batch decode or the continuous-batching engine.

Fixed batch (every family, the PR-4 path):

    PYTHONPATH=src python -m repro.launch.serve \
        --arch llama3.2-1b --smoke --batch 4 --prompt-len 64 --gen 32

Continuous batching over the paged compressed KV cache (dense/moe):

    PYTHONPATH=src python -m repro.launch.serve \
        --arch llama3.2-1b --smoke --mode engine --requests 6 \
        --stagger 2 --wire int8 --stream

The last stdout line is always a machine-readable JSON summary
(``benchmarks/serve_load.py`` consumes it); everything above it is for
humans. Timed paths carry no device→host syncs: prefill is timed through
one ``block_until_ready`` on the last-token logits, the decode loop
stacks tokens on device and is timed through a single trailing block
(``--stream`` adds per-token syncs by design — don't benchmark with it).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
import repro.obs as obs
from repro.dist import step as dstep
from repro.models import transformer
from repro.serve import ServeConfig, ServeEngine
from repro.utils.compile_cache import enable_compile_cache


def _prompt_batch(cfg, key_prompt, key_patch, b, prompt_len):
    if cfg.family == "audio":
        prompts = jax.random.randint(
            key_prompt, (b, cfg.num_codebooks, prompt_len), 0, cfg.vocab_size)
        return {"tokens": prompts}
    if cfg.family == "vlm":
        prompts = jax.random.randint(key_prompt, (b, prompt_len), 0, cfg.vocab_size)
        return {
            "tokens": prompts,
            "patch_embeds": jax.random.normal(
                key_patch, (b, cfg.num_patches, cfg.d_model)),
        }
    prompts = jax.random.randint(key_prompt, (b, prompt_len), 0, cfg.vocab_size)
    return {"tokens": prompts}


def run_fixed(cfg, params, args) -> dict:
    """Fixed-batch prefill + decode; returns the summary dict."""
    _, key_prompt, key_patch = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    b = args.batch
    cache_len = args.cache_len or (args.prompt_len + args.gen)
    batch = _prompt_batch(cfg, key_prompt, key_patch, b, args.prompt_len)

    prefill = jax.jit(dstep.make_prefill_step(cfg, cache_len=cache_len))
    serve = jax.jit(dstep.make_serve_step(cfg))

    t0 = time.time()
    last_logits, cache = prefill(params, batch)
    jax.block_until_ready(last_logits)
    t_prefill = time.time() - t0
    pos0 = args.prompt_len + (cfg.num_patches if cfg.family == "vlm" else 0)
    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)

    # Sync-free decode loop: the position advances on device (a host
    # `jnp.asarray(pos0 + i)` each step would re-upload a scalar and
    # serialize dispatch) and tokens stack on device; one trailing block
    # closes the timed region.
    pos = jnp.asarray(pos0, jnp.int32)
    generated = [tok]
    t0 = time.time()
    for _ in range(args.gen - 1):
        tok, logits, cache = serve(params, cache, tok, pos)
        pos = pos + 1
        generated.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = np.asarray(jnp.stack(generated, axis=-1))
    steps = max(args.gen - 1, 1)
    print(f"prefill: {b}x{args.prompt_len} tokens in {t_prefill*1e3:.1f} ms")
    print(f"decode:  {args.gen-1} steps x {b} seqs in {t_decode*1e3:.1f} ms "
          f"({t_decode/steps*1e3:.1f} ms/step)")
    print(f"sample continuations (token ids), first sequence: "
          f"{gen.reshape(b, -1)[0][:16]} ...")
    assert np.isfinite(np.asarray(last_logits)).all()
    return {
        "mode": "fixed",
        "arch": args.arch,
        "batch": b,
        "prompt_len": args.prompt_len,
        "gen": args.gen,
        "prefill_ms": t_prefill * 1e3,
        "decode_ms": t_decode * 1e3,
        "ms_per_step": t_decode / steps * 1e3,
        "tokens_per_s": (args.gen - 1) * b / t_decode if t_decode > 0 else 0.0,
    }


def run_engine(cfg, params, args) -> dict:
    """Continuous-batching engine over the paged cache; returns summary."""
    scfg = ServeConfig(
        max_slots=args.max_slots,
        page_size=args.page_size,
        pages_per_slot=args.pages_per_slot,
        prompt_pad=args.prompt_pad or args.prompt_len,
        max_new_tokens=args.gen,
        wire=args.wire,
    )
    if args.warmup:
        # Populate the in-process jit cache (prefill + decode shapes are
        # identical across engines of one ServeConfig) so the timed run
        # measures serving, not compilation.
        warm = ServeEngine(cfg, params, scfg)
        warm.submit(np.zeros((min(4, scfg.prompt_pad),), np.int32),
                    max_new_tokens=2)
        warm.run()

    eng = ServeEngine(cfg, params, scfg)
    key_prompt = jax.random.split(jax.random.PRNGKey(args.seed), 2)[1]
    prompts = np.asarray(jax.random.randint(
        key_prompt, (args.requests, args.prompt_len), 0, cfg.vocab_size),
        np.int32)
    for i in range(args.requests):
        eng.submit(prompts[i], arrival_tick=i * args.stagger)

    on_token = None
    if args.stream:
        # Streaming "detok": this repo serves randomly initialised models,
        # so detokenisation is the identity over token ids.
        def on_token(rid, token):
            print(f"  [req {rid}] {token}")

    completions, metrics = eng.run(on_token=on_token)
    print(f"engine:  {metrics['requests']} requests, wire={args.wire}, "
          f"{metrics['generated_tokens']} tokens in {metrics['wall_s']*1e3:.1f} ms "
          f"({metrics['tokens_per_s']:.1f} tok/s, "
          f"p50 {metrics['latency_p50_s']*1e3:.1f} ms, "
          f"p99 {metrics['latency_p99_s']*1e3:.1f} ms, "
          f"peak {metrics['peak_active_slots']} slots)")
    for c in completions[: min(3, len(completions))]:
        print(f"  req {c.rid}: admitted tick {c.admit_tick}, done tick "
              f"{c.done_tick}, tokens {c.tokens[:8].tolist()} ...")
    return {
        "mode": "engine",
        "arch": args.arch,
        "wire": args.wire,
        "requests": args.requests,
        "prompt_len": args.prompt_len,
        "gen": args.gen,
        "max_slots": args.max_slots,
        "page_size": args.page_size,
        "pages_per_slot": args.pages_per_slot,
        **{k: (float(v) if isinstance(v, float) else int(v))
           for k, v in metrics.items()},
    }


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", choices=("fixed", "engine"), default="fixed")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=0, help="0 -> prompt+gen")
    ap.add_argument("--seed", type=int, default=0)
    # engine mode
    ap.add_argument("--wire", default="float32",
                    choices=("float32", "float16", "bfloat16", "int8"))
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--stagger", type=int, default=0,
                    help="ticks between request arrivals")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages-per-slot", type=int, default=8)
    ap.add_argument("--prompt-pad", type=int, default=0,
                    help="0 -> prompt-len (must be a page multiple)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as generated (adds per-token syncs)")
    ap.add_argument("--warmup", action="store_true",
                    help="engine mode: compile-warm the jit cache before timing")
    ap.add_argument("--obs", action="store_true",
                    help="enable the repro.obs telemetry spine (JSONL events "
                         "+ metrics.prom/summary.json under --obs-dir)")
    ap.add_argument("--obs-dir", default="runs/obs-serve",
                    help="telemetry output directory (with --obs)")
    args = ap.parse_args(argv)

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    key_init = jax.random.split(jax.random.PRNGKey(args.seed), 3)[0]
    params = transformer.init_params(cfg, key_init)

    if args.obs:
        obs.configure(args.obs_dir)
        obs.get().event("run_start", run=f"serve-{args.arch}",
                        argv=sys.argv[1:], backend="serve", mode=args.mode,
                        wire=args.wire)
    try:
        if args.mode == "engine":
            summary = run_engine(cfg, params, args)
            obs.get().event("serve_summary",
                            requests=summary["requests"],
                            tokens_per_s=summary["tokens_per_s"],
                            peak_active_slots=summary["peak_active_slots"],
                            peak_pages=summary["peak_pages"],
                            page_pool_occupancy=summary["page_pool_occupancy"])
        else:
            summary = run_fixed(cfg, params, args)
            obs.get().event("summary", **summary)
    finally:
        if args.obs:
            obs.export.write_all(args.obs_dir)
            obs.shutdown()
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
