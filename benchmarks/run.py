"""Benchmark driver: one entry per paper table/figure + kernel micro +
comm-overhead unit economics. Prints ``name,us_per_call,derived`` CSV.

Default preset is CI-sized (CPU container); pass --preset paper for the
full Table-1 configuration of the paper.

  PYTHONPATH=src python -m benchmarks.run [--preset ci|paper] [--skip-fl]
                                          [--skip-scaling]

This process never imports JAX: every benchmark runs in a child process,
so on a TPU host each child can hold the chip in turn.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def _child(module: str, *args: str):
    """``benchmarks.<module>.run(*args)`` in a child process; forwards the
    child's output and returns its rows (the child's last output line)."""
    code = ("import json, sys; "
            f"from benchmarks import {module} as m; "
            "print(json.dumps(m.run(*sys.argv[1:])))")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", code, *args], env=env,
                          capture_output=True, text=True, cwd=ROOT)
    if proc.returncode != 0:
        raise RuntimeError(f"{module} failed:\n{proc.stderr[-3000:]}")
    *log, last = proc.stdout.splitlines()
    for line in log:
        print(line, flush=True)
    return json.loads(last)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="ci", choices=["ci", "paper"])
    ap.add_argument("--skip-fl", action="store_true",
                    help="skip the FL training benchmarks (tables/figures)")
    ap.add_argument("--skip-scaling", action="store_true",
                    help="skip the fake-device subprocess sweeps "
                         "(sim-engine scaling + dist_step grad-sync micro)")
    args = ap.parse_args()

    print("name,us_per_call,derived")

    # --- kernel microbenchmark (fast) ---------------------------------
    for r in _child("kernel_bench"):
        _row(r["name"], r["us_per_call"], r["derived"])

    # --- comm-overhead unit economics (fast, exact) -------------------
    for r in _child("comm_overhead"):
        tag = f"tau={r['tau']}" if r.get("sweep") else f"rate={r['rate']}"
        _row(
            f"comm_overhead/{r['scheme']}/{tag}",
            r["us_per_round"],
            f"total_gb={r['total_gb']:.4f};down_gb={r['download_gb']:.4f}",
        )

    if not args.skip_scaling:
        # --- simulation-engine scaling (vmap vs shard_map) --------------
        # Runs in a subprocess: the shard backend needs fake XLA devices,
        # which must be configured before jax initialises.
        from benchmarks import sim_scaling

        for r in sim_scaling.run(args.preset):
            _row(
                f"sim_scaling/{r['backend']}/{r['topology']}"
                f"/clients={r['clients']}",
                r["us_per_round"],
                f"rounds_per_sec={r['rounds_per_sec']};"
                f"bytes_per_round={r['bytes_per_round']};"
                f"ingress_bytes_per_round={r['ingress_bytes_per_round']};"
                f"devices={r['devices']}",
            )

        # --- distributed train step (grad-sync × wire dtype) ------------
        # Same subprocess isolation: the mesh needs fake XLA devices.
        from benchmarks import dist_step

        for r in dist_step.run(args.preset):
            _row(
                f"dist_step/{r['grad_sync']}/wire={r['wire_dtype']}",
                r["us_per_step"],
                f"up_mb={r['upload_mb_per_shard']};bcast_mb={r['broadcast_mb']};"
                f"dense_mb={r['dense_mb']};devices={r['devices']}",
            )

        # --- scheme-composition sweep (preset × selector × wire ×
        # downlink) — measures the stage registry's dispatch cost
        # (build/compile) and steady-state round time per composition on
        # the shard engine; the downlink rows keep the new server-state
        # path from rotting silently.
        from benchmarks import scheme_compose

        for r in scheme_compose.run(args.preset):
            _row(
                f"scheme_compose/{r['scheme']}/{r['selector']}/{r['wire']}"
                f"/dl_{r['downlink']}",
                r["us_per_round"],
                f"build_s={r['build_s']};bytes_per_round={r['bytes_per_round']};"
                f"devices={r['devices']}",
            )

    if not args.skip_fl:
        # --- Table 3 ---------------------------------------------------
        for r in _child("table3_cifar", args.preset):
            _row(
                f"table3/{r['scheme']}/emd={r['emd']}",
                r["seconds"] * 1e6,
                f"acc={r['accuracy']:.4f};comm_gb={r['comm_gb']:.4f}",
            )

        # --- Table 4 ---------------------------------------------------
        for r in _child("table4_shakespeare", args.preset):
            _row(
                f"table4/{r['scheme']}",
                r["seconds"] * 1e6,
                f"acc={r['accuracy']:.4f};comm_gb={r['comm_gb']:.4f}",
            )

        # --- Fig 4 ------------------------------------------------------
        curves = _child("fig4_curves", args.preset)
        for scheme, pts in curves.items():
            final = pts[-1]["accuracy"] if pts else float("nan")
            _row(f"fig4/{scheme}", 0.0, f"final_acc={final:.4f};points={len(pts)}")

        # --- Figs 5/6 ----------------------------------------------------
        for r in _child("fig5_fig6_sweep", args.preset):
            _row(
                f"fig5_6/{r['task']}/{r['scheme']}/rate={r['rate']}",
                r["seconds"] * 1e6,
                f"acc={r['accuracy']:.4f};comm_gb={r['comm_gb']:.4f}",
            )

    # --- roofline summary (if dry-run artifacts exist) -----------------
    from benchmarks import roofline

    rows = roofline.load("experiments/dryrun")
    ok = [r for r in rows if r.get("status") == "ok"]
    for r in ok:
        t = r["roofline_terms_s"]
        _row(
            f"roofline/{r['arch']}/{r['shape']}",
            t[r["dominant_term"]] * 1e6,
            f"dominant={r['dominant_term']};peak_gb={r['memory']['peak_bytes_per_chip']/1e9:.2f}",
        )
    print(f"# done ({len(ok)} roofline rows)", flush=True)


if __name__ == "__main__":
    main()
