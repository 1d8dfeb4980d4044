"""One run of one cell of the chip benchmark.

A cell (``BENCHMARK.json`` ``workloads``) names a model configuration and a
traffic mix. Everything that belongs to one of them is found by name under
``benchmarks/chip``: ``traffic/<traffic>.json`` (clients, cohort, batch,
scheme, data skew, the rounds the wire is counted over),
``limits/<cell>.json`` (the limit of each number that decides
``correct``), the configuration's own file, and for its
``family``: ``families/<family>.py`` (the system's model, the data
generator, the FLOP count) and ``reference/<family>.py`` (the plain model).
Each per-layer metric is read by ``metrics/<metric>.py``.

A run:

1. set-up: the persistent compile cache inside the checkout, the clients'
   data on the device, one ``FLSimulator`` over the vmap engine, and its
   first rounds through ``FLSimulator.run`` (the first compiles);
2. the window: the same simulator's ``run`` round after round, closed loop,
   until ``--seconds`` have passed and the traffic's ``wire_rounds`` are
   done; no evaluation, telemetry off;
3. with ``--trace 1``: a few more rounds under the profiler, reduced to the
   per-layer metrics;
4. the check: the program's state is freed and the plain reference follows
   the first rounds from the same seed and batches.

The last line of standard output is the result, a JSON object.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.chip import trace_reduce, wire
from benchmarks.chip.reference.fl_round import RoundReference

BENCH = Path("benchmarks") / "chip"      # the benchmark's directory in a checkout
WINDOW_SPAN = "bench.window"
BATCH_SPAN = "bench.batch_build"
FIRST_ROUNDS = 3        # rounds the reference follows
TRACE_SECONDS = 2.0     # the traced segment lasts this long ...
TRACE_MIN_ROUNDS = 3    # ... and holds at least this many rounds
# A leaf whose reference gradient is under this share of the median leaf's
# moves by rounding alone; it is left out of the norm gaps.
MOVED_SHARE = 1e-3


class Refused(SystemExit):
    """The run cannot be made here (no chip, unknown cell); exit non-zero
    without a result."""

    def __init__(self, msg: str):
        print(f"chip benchmark: {msg}", file=sys.stderr, flush=True)
        super().__init__(3)


# ---------------------------------------------------------------------------
# Finding the cell's files
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    root: Path
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def family(self) -> str:
        return self.config["family"]

    def module(self, kind: str, name: str):
        """``benchmarks/chip/<kind>/<name>.py`` of this checkout."""
        path = self.root / BENCH / kind / f"{name}.py"
        spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def _read(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise Refused(f"missing {path}") from None


def load_cell(root: Path, name: str) -> Cell:
    spec = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    wl = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == wl["config"])

    def applies(metric, reported=()):
        cells_of = metric.get("workloads")
        return name in cells_of if cells_of is not None else (
            not reported or metric["moves"] in reported)

    end_to_end = [m for m in spec["end_to_end"] if applies(m)]
    names = {m["name"] for m in end_to_end}
    return Cell(name=name, root=root, chips=wl["chips"],
                config=_read(root / config["file"]),
                traffic=_read(root / BENCH / "traffic" / f"{wl['traffic']}.json"),
                limits=_read(root / BENCH / "limits" / f"{name}.json"),
                end_to_end=end_to_end,
                per_layer=[m for m in spec["per_layer"] if applies(m, names)])


def load_peak(root: Path, kind: str) -> dict:
    peaks = _read(root / BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise Refused(f"no peak for device kind {kind!r} in peaks.json")
    return peaks[kind]


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent compile cache in ``<checkout>/.jax_cache``, with no
    size cap, holding every program: only a cell's first run in a checkout
    compiles."""
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_tpu: bool) -> dict:
    devices = jax.devices()
    d = devices[0]
    if require_tpu and d.platform != "tpu":
        raise Refused(f"needs a TPU, found platform {d.platform!r} ({d.device_kind}, "
                      f"{len(devices)} device(s))")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


class CompileWatch:
    """Backend compiles and persistent-cache hits since the last ``take``,
    read from ``jax.monitoring``."""

    def __init__(self):
        self.compiles, self.compile_s, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def take(self) -> dict:
        out = {"backend_compiles": self.compiles, "compile_s": self.compile_s,
               "cache_hits": self.hits}
        self.compiles, self.compile_s, self.hits = 0, 0.0, 0
        return out


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def _take(pools, ids, rows):
    return tuple(p[ids[:, None], rows] for p in pools)


class Batches:
    """The batch provider handed to ``FLSimulator.run``: each sampled
    client's next ``batch`` rows of its own pool, in an order drawn from the
    seed, one device gather per round. A client's rows repeat only once it
    has used its whole pool. While ``log`` is a list, each round's client ids
    and batches are appended to it."""

    def __init__(self, pools, batch: int, seed: int):
        self.pools = pools
        clients, self.size = pools[0].shape[:2]
        if self.size < FIRST_ROUNDS * batch:
            raise ValueError(f"a client's pool of {self.size} rows is under "
                             f"{FIRST_ROUNDS} batches of {batch}")
        self.batch = batch
        self.rng = np.random.default_rng([seed, 29])
        self.order = np.argsort(self.rng.random((clients, self.size)), axis=1)
        self.cursor = np.zeros(clients, np.int64)
        self.take = jax.jit(_take)
        self.log: list | None = None

    def __call__(self, round_idx, client_ids, rng):
        with jax.profiler.TraceAnnotation(BATCH_SPAN):
            rows = np.empty((len(client_ids), self.batch), np.int32)
            for j, c in enumerate(client_ids):
                if self.cursor[c] + self.batch > self.size:
                    self.order[c] = self.rng.permutation(self.size)
                    self.cursor[c] = 0
                rows[j] = self.order[c, self.cursor[c]:self.cursor[c] + self.batch]
                self.cursor[c] += self.batch
            out = self.take(self.pools, jnp.asarray(client_ids, jnp.int32),
                            jnp.asarray(rows))
        if self.log is not None:
            self.log.append((np.array(client_ids), out))
        return out


def program_fns(cell: Cell):
    """(init_fn, loss_fn) of the system under test."""
    return cell.module("families", cell.family).program(cell.config)


def build_simulator(cell: Cell, seed: int):
    from repro.core import CompressionConfig
    from repro.fl import FLConfig, FLSimulator

    t = cell.traffic
    init_fn, loss_fn = program_fns(cell)
    fl = FLConfig(num_clients=t["clients"], rounds=FIRST_ROUNDS,
                  clients_per_round=t["cohort"] if t["cohort"] < t["clients"] else 0,
                  batch_size=t["batch"], learning_rate=t["lr"], seed=seed,
                  backend="vmap")
    comp = CompressionConfig(scheme=t["scheme"], rate=t.get("rate", 1.0),
                             tau=t.get("tau", 0.0), alpha=t.get("alpha", 0.9),
                             beta=t.get("beta", 0.9), eps=t.get("eps", 1e-16))
    return FLSimulator(fl, comp, jax.jit(init_fn), loss_fn, None)


class RoundCounts:
    """Each round's per-client upload counts and download count, as the
    simulator hands them to the public ``record_round`` of its ledger
    (host values, which the simulator has already read back). The
    ledger's own arithmetic still runs; the benchmark keeps only the
    counts."""

    def __init__(self, sim):
        self.rounds: list[tuple[np.ndarray, int]] = []
        self._record = sim.ledger.record_round
        sim.ledger.record_round = self

    def __call__(self, upload_nnz, download_nnz, *args, **kwargs):
        self.rounds.append((np.asarray(upload_nnz, np.int64), int(download_nnz)))
        return self._record(upload_nnz, download_nnz, *args, **kwargs)

    def take(self, start: int, count: int) -> list[tuple[np.ndarray, int]]:
        """Rounds ``start`` .. ``start + count - 1``; an error where the
        simulator reported fewer."""
        got = self.rounds[start:start + count]
        if len(got) != count:
            raise RuntimeError(f"the simulator reported the counts of {len(self.rounds)} "
                               f"rounds to its ledger, not {start + count}")
        return got


@dataclasses.dataclass
class Trajectory:
    """What the first rounds produced, on the host: the parameters before
    and after, each round's broadcast, upload counts and download count,
    and per-leaf norms of the clients' compression state after."""

    paths: list
    theta0: list
    theta: list
    bcasts: list
    upload: list
    download: list
    state_norms: dict
    raw_grad: list | None = None   # reference only: first round's mean gradient


def _leaves(tree) -> tuple[list, list]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ([jax.tree_util.keystr(p) for p, _ in flat],
            [np.asarray(x, np.float32) for _, x in flat])


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def first_rounds(sim, batches: Batches, counts: RoundCounts) -> tuple[Trajectory, list]:
    """Drive ``sim`` through its first rounds; returns the trajectory and
    the rounds' (client ids, host batches) for the reference."""
    paths, theta0 = _leaves(sim.params)
    bcasts = []
    batches.log = []
    sim.fl.rounds = FIRST_ROUNDS
    sim.run(batches, on_round=lambda t, s: bcasts.append(_leaves(s.gbar_prev)[1]))
    log, batches.log = batches.log, None
    state_norms = {}
    for field in ("u", "v", "m"):
        tree = getattr(sim.cstates, field)
        if jax.tree_util.tree_leaves(tree):
            state_norms[field] = [_norm(x) for x in _leaves(tree)[1]]
    first = counts.take(0, FIRST_ROUNDS)
    traj = Trajectory(paths=paths, theta0=theta0, theta=_leaves(sim.params)[1],
                      bcasts=bcasts, upload=[u for u, _ in first],
                      download=[d for _, d in first], state_norms=state_norms)
    rounds = [(ids, jax.tree.map(np.asarray, b)) for ids, b in log]
    return traj, rounds


def start(cell: Cell, seed: int):
    """Set-up up to the window: the clients' data, the simulator, and its
    first rounds. Returns (simulator, batches, round counts, the program's
    trajectory, the first rounds' inputs)."""
    t = cell.traffic
    family = cell.module("families", cell.family)
    batches = Batches(family.make_pools(cell.config, t, seed), t["batch"], seed)
    sim = build_simulator(cell, seed)
    counts = RoundCounts(sim)
    prog, rounds = first_rounds(sim, batches, counts)
    return sim, batches, counts, prog, rounds


class _WindowClosed(Exception):
    pass


def run_rounds(sim, batches: Batches, seconds: float, min_rounds: int = 1) -> tuple:
    """``sim.run`` round after round until ``seconds`` have passed and at
    least ``min_rounds`` are done; returns the start and each round's end
    on the host clock."""
    stamps = []

    def on_round(t, s):
        jax.block_until_ready(s.params)
        now = time.perf_counter()
        stamps.append(now)
        if now >= deadline and len(stamps) >= min_rounds:
            raise _WindowClosed

    sim.fl.rounds = 2**31 - 1
    t0 = time.perf_counter()
    deadline = t0 + seconds
    try:
        sim.run(batches, on_round=on_round)
    except _WindowClosed:
        pass
    return t0, stamps


def round_hlo(sim, batches: Batches) -> str:
    """Optimised HLO text of the round program the window ran (from the
    compile cache), for the trace's scope paths."""
    t = sim.fl
    ids = np.sort(np.random.default_rng(0).choice(t.num_clients, sim.sampled_per_round,
                                                  replace=False))
    args = (sim.params, sim.cstates, sim.sstate, sim.gbar_prev, jnp.asarray(ids),
            batches(0, ids, None), jnp.asarray(0),
            jnp.asarray(t.learning_rate, jnp.float32), sim.tau_ctl.tau)
    return sim.engine.round_fn.lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# The plain reference and the comparison
# ---------------------------------------------------------------------------


def reference_trajectory(cell: Cell, seed: int, rounds: list, dtype=jnp.float32,
                         batch_share: float = 1.0, precision: str | None = None
                         ) -> Trajectory:
    """The plain reference over the recorded rounds, from its own initial
    weights for ``seed``, in float32 at the matmul precision the
    configuration states. ``dtype`` and ``batch_share`` below their
    defaults give the precision control and the half-batch fault (the loss
    means over the first share of each batch); ``precision`` overrides the
    configuration's."""
    ref = cell.module("reference", cell.family)
    params0 = jax.jit(lambda key: ref.init(key, cell.config))(jax.random.PRNGKey(seed))
    treedef = jax.tree_util.tree_structure(params0)
    paths, theta0 = _leaves(params0)
    prec = lax.Precision(precision or cell.config["matmul_precision"])
    if dtype != jnp.float32:
        prec = lax.Precision.DEFAULT
    keep = max(1, int(cell.traffic["batch"] * batch_share))
    grad = jax.jit(jax.grad(lambda p, b: ref.loss(p, b, cell.config, dtype, prec, keep)))
    rr = RoundReference(theta0, cell.traffic)
    bcasts, upload, download, raw = [], [], [], None
    for ids, batch in rounds:

        def grad_of(j, leaves):
            b = tuple(x[j] for x in batch)
            return _leaves(grad(jax.tree_util.tree_unflatten(treedef, leaves), b))[1]

        out = rr.round(ids, grad_of)
        raw = out["raw_grad"] if raw is None else raw
        bcasts.append(out["bcast"])
        upload.append(out["upload"])
        download.append(out["download"])
    fields = {"dgcwgmf": "uvm", "dgc": "uv", "none": ""}[rr.scheme]
    state_norms = {f: [float(np.sqrt(sum(_norm(st[f][i]) ** 2 for st in rr.states.values())))
                       for i in range(len(paths))] for f in fields}
    return Trajectory(paths=paths, theta0=theta0, theta=rr.params, bcasts=bcasts,
                      upload=upload, download=download, state_norms=state_norms,
                      raw_grad=raw)


def leaf_gaps(got: list, ref: list, moved: np.ndarray) -> np.ndarray:
    """Each moved leaf's gap between two per-leaf norms, over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    got, ref = np.asarray(got, np.float64)[moved], np.asarray(ref, np.float64)[moved]
    return np.abs(got - ref) / np.maximum(ref, np.median(ref))


def compare(prog: Trajectory, ref: Trajectory) -> dict:
    """The numbers a cell's limits can hold (see PERF.md for each): norm
    gaps by the worst leaf (``*_gap``) and by the median leaf (``*_med``)
    of the first round's broadcast (``grad``: the gradient as the update
    gets it), of every round's (``bcast``), of the parameters' change and
    of the compression state after the first rounds; and count gaps."""
    if prog.paths != ref.paths:
        raise AssertionError(f"parameter leaves differ: {prog.paths} vs {ref.paths}")
    raw = np.asarray([_norm(g) for g in ref.raw_grad])
    moved = raw >= MOVED_SHARE * np.median(raw)
    norms = lambda leaves: [_norm(x) for x in leaves]
    delta = lambda t: [a - b for a, b in zip(t.theta, t.theta0, strict=True)]
    bcast = [leaf_gaps(norms(p), norms(r), moved)
             for p, r in zip(prog.bcasts, ref.bcasts, strict=True)]
    gaps = {"grad": bcast[:1], "bcast": bcast,
            "delta": [leaf_gaps(norms(delta(prog)), norms(delta(ref)), moved)]}
    if ref.state_norms or prog.state_norms:
        if set(prog.state_norms) != set(ref.state_norms):
            raise AssertionError(f"state fields differ: {sorted(prog.state_norms)} vs "
                                 f"{sorted(ref.state_norms)}")
        gaps["state"] = [leaf_gaps(prog.state_norms[f], ref.state_norms[f], moved)
                         for f in sorted(ref.state_norms)]
    out = {}
    for name, g in gaps.items():
        out[f"{name}_gap"] = float(max(np.max(x) for x in g))
        out[f"{name}_med"] = float(max(np.median(x) for x in g))
    out["upload_gap"] = max(abs(int(p.sum()) - int(r.sum())) / int(r.sum())
                            for p, r in zip(prog.upload, ref.upload, strict=True))
    out["download_gap"] = max(abs(p - r) / r for p, r in zip(prog.download, ref.download,
                                                            strict=True))
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct``, and each number the cell's limits hold beside its limit."""
    checks = {k: {"value": numbers[k], "limit": v} for k, v in limits.items()
              if not k.startswith("_")}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return bool(ok), checks


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader sees."""

    view: trace_reduce.TraceView
    rounds: int
    round_ms: float
    flops_per_round: float
    peak: dict


def _free():
    gc.collect()
    jax.clear_caches()


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        device: dict, peak: dict) -> dict:
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    watch = CompileWatch()

    # 1. set-up
    sim, batches, counts, prog, rounds = start(cell, seed)
    setup = watch.take()
    # a program loaded from the persistent cache counts as a compile and a hit
    log(f"set-up: {json.dumps(setup)}; every program from the cache: "
        f"{setup['cache_hits'] >= setup['backend_compiles']}")

    # 2. the window: it lasts ``seconds`` and holds at least the rounds the
    # wire is counted over, the same window rounds in every run
    wire_rounds = cell.traffic["wire_rounds"]
    t0, stamps = run_rounds(sim, batches, seconds, wire_rounds)
    setup_s = t0 - t_start
    inside = watch.take()
    log(f"window: {len(stamps)} rounds in {stamps[-1] - t0} s; compiles inside it: "
        f"{json.dumps(inside)}")
    durations_ms = np.diff([t0, *stamps]) * 1e3
    log(f"window: median round {np.median(durations_ms)} ms, the five longest "
        f"{np.sort(durations_ms)[-5:].tolist()} ms")
    n = sim.total_params
    wire_mb = [wire.round_bytes(u, d, n) / 1e6
               for u, d in counts.take(FIRST_ROUNDS, wire_rounds)]
    e2e = {
        "setup_s": setup_s,
        "round_ms": (stamps[-1] - t0) * 1e3 / len(stamps),
        "round_ms_p90": float(np.percentile(durations_ms, 90)),
        "wire_mb_per_round": float(np.mean(wire_mb)),
    }
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))

    result: dict = {"correct": False, "attempted": len(stamps), "failed": 0}
    if trace:
        result["metrics"], result["breakdown"], busy = per_layer(
            cell, sim, batches, e2e["round_ms"], peak)
        device.update(busy)
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device

    # 3. the check, with the program's state freed
    del sim, batches, counts
    _free()
    ref = reference_trajectory(cell, seed, rounds)
    numbers = compare(prog, ref)
    log(f"readings: {json.dumps(numbers)}")
    result["correct"], result["checks"] = judge(numbers, cell.limits)
    watch.close()
    return result


def per_layer(cell: Cell, sim, batches: Batches, round_ms: float, peak: dict):
    """A traced segment after the window, reduced by each per-layer
    metric's reader."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            _, stamps = run_rounds(sim, batches, TRACE_SECONDS, TRACE_MIN_ROUNDS)
        jax.profiler.stop_trace()
        view = trace_reduce.load(d, round_hlo(sim, batches), WINDOW_SPAN)
    t = cell.traffic
    flops = cell.module("families", cell.family).forward_flops(cell.config, t)
    ctx = LayerContext(view=view, rounds=len(stamps), round_ms=round_ms,
                       flops_per_round=3.0 * flops * t["cohort"] * t["batch"], peak=peak)
    metrics = {}
    for m in cell.per_layer:
        value = cell.module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = {"busy_s": view.busy_ns() * 1e-9, "window_s": view.window_ns * 1e-9}
    return metrics, view.breakdown(), busy


def parse(argv):
    ap = argparse.ArgumentParser(description="one run of one chip-benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, root: Path, t_start: float) -> int:
    args = parse(argv)
    cell = load_cell(root, args.workload)
    enable_compile_cache(root)
    device = device_info(cell.chips, require_tpu=True)
    peak = load_peak(root, device["kind"])
    result = run(cell, args.seed, args.seconds, bool(args.trace), t_start, device, peak)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr,
              flush=True)
    return 0
