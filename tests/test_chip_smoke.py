"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script's phases run here with the Pallas kernels in interpret mode, the
four-chip phase on four virtual CPU devices, and the script itself must
refuse to run without a TPU. Its real-size run needs the chip.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(depth=8, batch=4, train_size=400)


def _phases(out: str) -> dict:
    return {line[1:line.index("]")]: json.loads(line.split("] ", 1)[1])
            for line in out.splitlines() if line.startswith("[")}


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    return env


def test_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=_env(), capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "found platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_one_chip_phases_at_tiny_size(capsys):
    watch = chip_smoke.CompileWatch()
    try:
        chip_smoke.run_one_chip(chip_smoke.Setup(clients=4, **TINY), watch)
    finally:
        watch.close()
    phases = _phases(capsys.readouterr().out)
    assert set(phases) == {"reference", "kernels", "cpu"}
    ref = phases["reference"]
    assert len(ref["steady_ms_per_round"]) == chip_smoke.TIMED_ROUNDS
    assert ref["upload_nnz_per_client"] > 0
    kern = phases["kernels"]
    assert kern["interpret"] and not kern["tpu_custom_call"]
    assert kern["mask_agreement"] >= chip_smoke.MIN_MASK_AGREEMENT
    assert kern["upload_nnz_kernels"] == kern["upload_nnz_ref"]
    # "chip" and host are the same CPU here: the rounds agree exactly.
    assert phases["cpu"]["max_abs_dparams"] == 0.0
    assert phases["cpu"]["upload_nnz_diff"] == 0.0


def test_four_device_phase_at_tiny_size():
    code = ("import chip_smoke as cs; "
            f"cs.run_four_chips(cs.Setup(clients=8, **{TINY!r}), "
            "cs.CompileWatch())")
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr[-3000:]
    result = _phases(r.stdout)["shard_vs_vmap"]
    assert result["batches_split"]
    assert set(result["min_devices_per_array"].values()) == {4}
    assert result["rel_dledger"] <= chip_smoke.MAX_REL_DLEDGER_SHARD
