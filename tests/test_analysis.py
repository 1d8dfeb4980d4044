"""Tests for the ``repro.analysis`` static-analysis subsystem.

Three layers, mirroring the analyzer families:

* **Lints** — every rule in the registry must fire exactly at the
  ``# expect: REPxxx`` annotations in its ``tests/analysis_corpus/``
  seeded-violation file and stay silent on the clean twin.  The corpus
  is the executable specification: adding a rule without a corpus pair
  fails ``test_every_rule_has_corpus_pair``.
* **Contracts** — the shipped registry passes ``check_all``; a
  deliberately broken stage (compensator that downcasts its state to
  bfloat16) registered just for the test is rejected with a
  CONTRACT-STATE finding, then cleaned out of the registry.
* **Jaxpr/collective gate** — the single-device config audits clean
  in-process and matches the committed baseline; a subprocess with 8
  fake devices re-audits the sharded configs against the baseline and
  demonstrates the gate by splicing a real extra ``psum`` into a
  report and asserting ``check_baseline`` rejects it.

Multi-device pieces run in a subprocess because ``XLA_FLAGS`` must be
set before jax initialises (same isolation as ``tests/test_dist.py``).
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import lints
from repro.analysis.findings import Finding, to_json
from repro.analysis.lints import rules as _rules  # noqa: F401  (registers rules)

REPO = Path(__file__).resolve().parents[1]
CORPUS = REPO / "tests" / "analysis_corpus"
EXPECT = re.compile(r"#\s*expect:\s*(REP\d+)")


def _expected_lines(path: Path) -> dict[int, set[str]]:
    out: dict[int, set[str]] = {}
    for lineno, line in enumerate(path.read_text().splitlines(), 1):
        for rule_id in EXPECT.findall(line):
            out.setdefault(lineno, set()).add(rule_id)
    return out


def _found_lines(path: Path) -> dict[int, set[str]]:
    out: dict[int, set[str]] = {}
    for f in lints.lint_source(path.read_text(), str(path)):
        out.setdefault(f.line, set()).add(f.rule)
    return out


# ---------------------------------------------------------------------------
# Lint rules vs the corpus
# ---------------------------------------------------------------------------

def test_every_rule_has_corpus_pair():
    for rule_id in lints.RULES:
        stem = rule_id.lower()
        assert (CORPUS / f"{stem}_bad.py").exists(), (
            f"{rule_id} has no seeded-violation file {stem}_bad.py — every "
            "rule ships with corpus evidence that it fires")
        assert (CORPUS / f"{stem}_ok.py").exists(), (
            f"{rule_id} has no clean twin {stem}_ok.py — every rule ships "
            "with evidence that it does NOT overfire")


@pytest.mark.parametrize("rule_id", sorted(lints.RULES))
def test_rule_fires_exactly_at_annotations(rule_id):
    bad = CORPUS / f"{rule_id.lower()}_bad.py"
    expected = _expected_lines(bad)
    found = _found_lines(bad)
    assert expected == found, (
        f"{bad.name}: annotated {expected} but linter found {found}")
    # the file under test is dedicated to this rule
    fired = {r for rules_ in found.values() for r in rules_}
    assert fired == {rule_id}, f"{bad.name} fired foreign rules: {fired}"


@pytest.mark.parametrize("rule_id", sorted(lints.RULES))
def test_clean_twin_is_silent(rule_id):
    ok = CORPUS / f"{rule_id.lower()}_ok.py"
    found = _found_lines(ok)
    assert not found, f"{ok.name} should be clean but fired: {found}"


def test_noqa_suppresses_and_scopes_to_rule():
    src = (
        "import jax\n"
        "key = jax.random.PRNGKey(0)\n"
        "a = jax.random.normal(key)\n"
        "b = jax.random.normal(key)  # repro-noqa: REP001\n"
        "c = jax.random.normal(key)  # repro-noqa: REP002\n"
    )
    found = lints.lint_source(src, "<noqa>")
    # line 4 suppressed (right rule id), line 5 still fires (wrong rule id)
    assert [f.line for f in found] == [5]
    bare = src.replace("# repro-noqa: REP002", "# repro-noqa")
    assert lints.lint_source(bare, "<noqa>") == []


def test_syntax_error_becomes_rep000_finding():
    found = lints.lint_source("def broken(:\n", "<bad>")
    assert [f.rule for f in found] == ["REP000"]


def test_tree_is_clean():
    """Satellite (a) stays true: the shipped tree has zero lint findings."""
    paths = [REPO / p for p in ("src", "benchmarks", "examples", "tests", "tools")]
    found = lints.lint_paths([p for p in paths if p.exists()])
    assert found == [], "\n".join(f.format() for f in found)


# ---------------------------------------------------------------------------
# Contract checks over the live registry
# ---------------------------------------------------------------------------

def test_shipped_presets_pass_contracts():
    from repro.analysis import contracts
    from repro.core.registry import PRESETS

    findings = contracts.check_all(presets=sorted(PRESETS))
    assert findings == [], "\n".join(f.format() for f in findings)


def test_broken_stage_is_rejected_then_cleaned_up(registry_sandbox):
    """A compensator that downcasts its state to bfloat16 must trip the
    state-fixed-point contract; the registry_sandbox fixture guarantees it
    cannot leak into the registry past the test (even on assertion
    failure, which the old hand-rolled try/finally cleanup could not)."""
    import jax.numpy as jnp
    from jax import tree_util

    from repro.analysis import contracts
    from repro.core import stages
    from repro.core.registry import SchemeSpec, register_preset

    tree_map = tree_util.tree_map

    @stages.register("compensator", "_broken_test")
    class _DowncastingEF(stages.Compensator):  # noqa: F841
        uses_v = True
        description = "test-only: accumulates in bfloat16 (contract violation)"

        def accumulate(self, cfg, ops, u, v, grad, extra):
            v = tree_map(jnp.add, v, grad)
            return v, u, v

        def extract(self, cfg, ops, u, v, value, masks):
            if masks is None:
                g_out, v = v, tree_map(lambda vv: vv * 0.0, v)
            else:
                g_out = tree_map(jnp.multiply, v, masks)
                v = tree_map(lambda vv, mk: vv * (1.0 - mk), v, masks)
            # the seeded bug: residual state persisted in half precision
            v = tree_map(lambda vv: vv.astype(jnp.bfloat16), v)
            return g_out, u, v

    register_preset(
        "_broken_test", SchemeSpec(selector="topk", compensator="_broken_test"))
    findings = contracts.check_preset("_broken_test")
    assert findings, "bfloat16 state downcast slipped through the contracts"
    assert any(f.rule == "CONTRACT-STATE" for f in findings), (
        "\n".join(f.format() for f in findings))
    assert any("bfloat16" in f.message for f in findings)


def test_registry_sandbox_restores_registry():
    """The fixture's cleanup really ran: the previous test's throwaway
    stage and preset are gone from the live registry."""
    from repro.analysis import contracts
    from repro.core import stages

    assert "_broken_test" not in stages.REGISTRY["compensator"]
    with pytest.raises(ValueError, match="_broken_test"):
        contracts.check_preset("_broken_test")


# ---------------------------------------------------------------------------
# Jaxpr audit + collective baseline
# ---------------------------------------------------------------------------

def test_dryrun_shares_the_collective_parser():
    """The one-off dry-run tool and the standing gate must count
    collectives with the same code, or they will drift apart."""
    from repro.analysis import jaxpr_audit

    saved = os.environ.get("XLA_FLAGS")
    try:
        from repro.launch import dryrun
        assert dryrun.parse_collective_bytes is jaxpr_audit.parse_collective_bytes
    finally:
        # dryrun sets XLA_FLAGS at import; don't leak it to later subprocesses
        if saved is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = saved


def test_collective_counts_parses_hlo_text():
    from repro.analysis.jaxpr_audit import collective_counts

    hlo = (
        "  %ar = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}\n"
        "  %ag.1 = f32[16]{0} all-gather(f32[8]{0} %y), dimensions={0}\n"
        "  %ar.2 = f32[4]{0} all-reduce-start(f32[4]{0} %z)\n"
    )
    counts = collective_counts(hlo)
    assert counts == {"all-reduce": 2, "all-gather": 1}


def test_single_device_config_audits_clean_and_matches_baseline():
    from repro.analysis import jaxpr_audit

    findings, report = jaxpr_audit.audit_config("vmap_dgcwgmf")
    assert findings == [], "\n".join(f.format() for f in findings)
    assert "skipped" not in report
    baseline = json.loads((REPO / jaxpr_audit.DEFAULT_BASELINE).read_text())
    pinned = baseline["configs"]["vmap_dgcwgmf"]
    assert report["counts"] == pinned["counts"]
    assert report["num_collectives"] == pinned["num_collectives"]


def test_multi_device_configs_skip_gracefully_on_one_device():
    import jax

    from repro.analysis import jaxpr_audit

    if jax.device_count() >= 8:
        pytest.skip("host actually has 8 devices; nothing to gate")
    findings, report = jaxpr_audit.audit_config("shard_dgcwgmf")
    assert findings == []
    assert "skipped" in report
    # a skipped config must not raise baseline findings either
    assert jaxpr_audit.check_baseline({"shard_dgcwgmf": report}) == []


def test_check_baseline_flags_missing_file(tmp_path):
    from repro.analysis import jaxpr_audit

    findings, report = jaxpr_audit.audit_config("vmap_dgcwgmf")
    assert findings == []
    missing = tmp_path / "nope.json"
    out = jaxpr_audit.check_baseline({"vmap_dgcwgmf": report}, missing)
    assert [f.rule for f in out] == ["JAXPR-BASELINE"]
    assert "write-baseline" in out[0].message


_GATE_SCRIPT = r"""
import os
assert "--xla_force_host_platform_device_count=8" in os.environ["XLA_FLAGS"]
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis import jaxpr_audit

# 1) the committed baseline matches a fresh audit of every config
findings, reports = jaxpr_audit.audit_all()
assert not findings, [f.format() for f in findings]
assert not any("skipped" in r for r in reports.values()), reports
drift = jaxpr_audit.check_baseline(reports)
assert not drift, [f.format() for f in drift]

# 2) gate demo: compile a REAL extra psum, splice its collectives into a
#    pinned config's report, and the baseline check must reject it
mesh = Mesh(np.array(jax.devices()), ("d",))
extra_fn = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "d"),
                                 mesh=mesh, in_specs=P("d"), out_specs=P()))
hlo = extra_fn.lower(jnp.zeros((8, 4), jnp.float32)).compile().as_text()
extra = jaxpr_audit.collective_counts(hlo)
assert sum(extra.values()) >= 1, f"psum compiled to no collective: {extra!r}"

doctored = dict(reports["shard_dgcwgmf"])
counts = dict(doctored["counts"])
for kind, n in extra.items():
    counts[kind] = counts.get(kind, 0) + n
doctored["counts"] = counts
doctored["num_collectives"] = sum(counts.values())
bad = jaxpr_audit.check_baseline({"shard_dgcwgmf": doctored})
assert bad and all(f.rule == "JAXPR-BASELINE" for f in bad), \
    [f.format() for f in bad]
assert any("shard_dgcwgmf" in f.path for f in bad), [f.format() for f in bad]
assert any("analysis-baseline" in f.message for f in bad)
print("GATE-OK")
"""


@pytest.mark.slow
def test_collective_gate_subprocess_8dev():
    """End-to-end on 8 fake devices: fresh audit matches the committed
    baseline, and a deliberately added psum fails the gate."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-c", _GATE_SCRIPT],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}")
    assert "GATE-OK" in proc.stdout


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _run_cli(*args, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_cli_lint_exit_codes(tmp_path):
    bad = CORPUS / "rep001_bad.py"
    proc = _run_cli("--lint", str(bad))
    assert proc.returncode == 1, proc.stdout
    assert "REP001" in proc.stdout

    out = tmp_path / "report.json"
    proc = _run_cli("--lint", "--json", str(out), str(CORPUS / "rep001_ok.py"))
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr[-2000:]}"
    payload = json.loads(out.read_text())
    assert payload["ok"] is True and payload["findings"] == []


@pytest.mark.slow
def test_cli_rule_filter(tmp_path):
    # rep003_bad also has REP001-free content; --rule REP001 must silence it
    proc = _run_cli("--lint", "--rule", "REP001", str(CORPUS / "rep003_bad.py"))
    assert proc.returncode == 0, proc.stdout


# ---------------------------------------------------------------------------
# Findings plumbing
# ---------------------------------------------------------------------------

def test_to_json_shape():
    f = Finding(rule="REP001", path="x.py", line=3, message="m")
    payload = json.loads(to_json([f], extra={"families": ["lint"]}))
    assert payload["version"] == 1
    assert payload["ok"] is False
    assert payload["num_findings"] == 1
    assert payload["findings"][0]["rule"] == "REP001"
    assert payload["families"] == ["lint"]
