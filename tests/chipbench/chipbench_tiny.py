"""A checkout-shaped directory holding the chip benchmark with tiny cells,
for driving the harness on the CPU."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_CONFIGS = {
    "resnet8_tiny": {"family": "resnet_cifar", "depth": 8, "widths": [16, 32, 64],
                     "num_classes": 10, "image_shape": [32, 32, 3], "norm_groups": 8,
                     "matmul_precision": "default"},
    "lstm_tiny": {"family": "char_lstm", "vocab_size": 80, "embed_dim": 8,
                  "hidden_size": 32, "num_layers": 1, "matmul_precision": "default"},
}
TINY_TRAFFIC = {
    "dgcwgmf-4x8": {"scheme": "dgcwgmf", "rate": 0.1, "tau": 0.6, "lr": 0.1,
                    "clients": 4, "cohort": 4, "batch": 8, "samples_per_client": 32,
                    "emd": 1.35, "noise": 0.55, "wire_rounds": 2},
    "dgcwgmf-3of6": {"scheme": "dgcwgmf", "rate": 0.1, "tau": 0.6, "lr": 0.5,
                     "clients": 6, "cohort": 3, "batch": 4, "seq_len": 12,
                     "samples_per_client": 16, "client_mix": 0.35,
                     "alpha_shared": 0.3, "alpha_own": 0.15, "wire_rounds": 2},
}
TINY_CELLS = {
    "resnet8_tiny-dgcwgmf-4x8": ("resnet8_tiny", "dgcwgmf-4x8"),
    "lstm_tiny-dgcwgmf-3of6": ("lstm_tiny", "dgcwgmf-3of6"),
}
# Tiny cells run on the CPU, at full float32: the program agrees with the
# reference far inside these.
TINY_LIMITS = {"bcast_gap": 1e-3, "delta_gap": 1e-3, "state_gap": 1e-3,
               "upload_gap": 1e-3, "download_gap": 1e-3}


def read_spec(root: Path = REPO) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def per_layer_names(spec: dict, cell: str) -> set[str]:
    """Names of the per-layer metrics that ``spec`` asks of ``cell``: the
    entries whose ``workloads`` list it, and those without the key that move
    an end-to-end metric the cell reports."""
    reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
    return {m["name"] for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in reported)}


def config_family(spec: dict, root: Path, config: str) -> str:
    """The ``family`` that ``config``'s file under ``root`` names."""
    file = next(c["file"] for c in spec["configs"] if c["name"] == config)
    return json.loads((root / file).read_text())["family"]


def family_per_layer(spec: dict, root: Path, family: str) -> set[str]:
    """Per-layer names of every cell of ``spec`` whose configuration (its
    file under ``root``) is of ``family``."""
    return set().union(*(per_layer_names(spec, w["name"]) for w in spec["workloads"]
                         if config_family(spec, root, w["config"]) == family))


def tiny_root(tmp: Path, configs=None, traffic=None, cells=None, limits=None) -> Path:
    """``tmp`` laid out as a checkout: BENCHMARK.json with ``cells`` (name ->
    (config, traffic)) and the benchmark's files, plus the given new
    configuration and traffic files. A new cell takes the per-layer entries
    of the accepted cells whose configuration is of its family."""
    configs = TINY_CONFIGS if configs is None else configs
    traffic = TINY_TRAFFIC if traffic is None else traffic
    cells = TINY_CELLS if cells is None else cells
    bench = tmp / "benchmarks" / "chip"
    shutil.copytree(REPO / "benchmarks" / "chip", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    accepted, spec = read_spec(), read_spec()
    for name, cfg in configs.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "source": "test", "reduced": [],
                                "file": f"benchmarks/chip/configs/{name}.json",
                                "why": "tiny"})
    for name, mix in traffic.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for name, (cfg, mix) in cells.items():
        (bench / "limits" / f"{name}.json").write_text(json.dumps(limits or TINY_LIMITS))
        spec["workloads"].append({"name": name, "config": cfg, "traffic": mix,
                                  "chips": 1, "why": "tiny"})
        family = configs[cfg]["family"] if cfg in configs else config_family(accepted, REPO, cfg)
        inherited = family_per_layer(accepted, REPO, family)
        for m in spec["per_layer"]:
            if m["name"] in inherited and "workloads" in m:
                m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


CPU_PEAK = {"bf16_flops_per_s": 1e12}   # a stand-in; no CPU number is a device metric


def run_cell(root: Path, name: str, *, seed=5, seconds=0.5, trace=False) -> dict:
    """The harness's run on the CPU, past its look for a chip."""
    import time

    from benchmarks.chip import harness

    cell = harness.load_cell(root, name)
    device = harness.device_info(cell.chips, require_tpu=False)
    return harness.run(cell, seed, seconds, trace, time.perf_counter(), device, CPU_PEAK)
