"""One run of one cell: set-up, the measured window, metrics, ``correct``.

The system under test is the served query path as users reach it: a
``ServingFront`` with one shared tenant over HTTP (``serve_http``), its
microbatching ``AqpService`` and the fused scan on the chip, with the
engine settings the configuration states. Clients run in a child process
that never imports JAX, so the server process alone holds the chip.

Set-up, in order: the relation made on the device from the seed; the front
and its engine (which draws the sample); the warm-up, which drives every
shape the window uses (microbatches of each size, then the clients' own
warm-up requests). The window then runs for ``--seconds``.

After the window the program's answers are held against the reference
(``reference``) and the program's state is freed first, so the reference's
own device work never sets the run's memory peak. Two layers are held: the
raw scan estimates of a sample of answers (against the sample rows), and a
sample of the served answers with their bounds (against the exact answers
over the whole relation).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

from chipbench import datagen, e2e, queries, reference, tracing

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
CLIENTS = Path(__file__).resolve().parent / "clients.py"
PATH = "/v1/tenants/shared/execute"


class NothingToRun(RuntimeError):
    """Nothing to run: JAX found no accelerator or fewer chips than the
    cell asks for, or no such cell."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    metrics_dir: Path


def load_cell(name: str, root: Path = ROOT, workload: Optional[dict] = None
              ) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration, its
    traffic mix and the metrics it reports, all found by name.

    ``workload``: an entry in the form of ``BENCHMARK.json``'s
    ``workloads`` for a cell the file does not list (its configuration is
    then ``configs/<config>.json``). The CPU tests run ``tpch_sf10.adhoc``
    so: its runs on the chip spread too widely for a bound (PERF.md), and it
    is the one mix with group-by, COUNT and SUM cells, which the reference
    has to handle."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench_dir = root / BENCH_DIR.relative_to(ROOT)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload is not None:
        cells[name] = workload
    if name not in cells:
        raise NothingToRun(f"no workload {name!r} in BENCHMARK.json; known: "
                           f"{sorted(cells)}")
    w = cells[name]
    files = {c["name"]: root / c["file"] for c in bench["configs"]}
    config = json.loads(files.get(
        w["config"], bench_dir / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())

    def reported(m):
        return name in m.get("workloads", [name])

    e2e_metrics = [m for m in bench["end_to_end"] if reported(m)]
    e2e_names = {m["name"] for m in e2e_metrics}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in e2e_names and reported(m)]
    return Cell(name, int(w["chips"]), config, traffic, e2e_metrics,
                per_layer, bench_dir / "metrics")


def metric_reader(name: str, metrics_dir: Path = BENCH_DIR / "metrics"):
    """``<metrics_dir>/<name>.py``'s ``read(ctx)``: a number, or None when
    the run had nothing for it to read."""
    path = Path(metrics_dir) / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """Backend compiles since construction (copied from ``chip_smoke.py``):
    a ``jax.monitoring`` listener on XLA's backend-compile event."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def use_compile_cache():
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept,
    however quickly it compiled, so only a checkout's first run compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _log(*args):
    print(*args, file=sys.stderr, flush=True)


def _chunks(seq, sizes):
    i = 0
    for n in sizes:
        if i >= len(seq):
            return
        yield seq[i:i + n]
        i += n


class Capture:
    """Wraps the tenant service's ``_execute_slice`` -- one fused execution
    of a microbatch -- to keep each query's engine result (its raw snippet
    answers are compared with the reference after the window) and the scan
    work the executor counted for it (``roofline.scan_bytes``)."""

    def __init__(self, service, schema):
        self.results = {}
        self.scan_calls = 0
        self.scan_bytes = 0
        self._service = service
        self._schema = schema
        self._orig = service._execute_slice
        service._execute_slice = self._execute

    def _execute(self, batch):
        from chipbench.roofline import scan_bytes

        out = self._orig(batch)
        for q, r in zip(batch, out):
            self.results[q] = r
        stats = self._service.executor.stats
        if stats.eval_calls:
            s = self._schema
            measures = {a.measure for q in batch for a in q.aggs
                        if a.kind in ("AVG", "SUM") and a.measure is not None}
            self.scan_calls += stats.eval_calls
            self.scan_bytes += scan_bytes(
                stats.tuples_scanned, s.n_num, s.n_cat, len(measures),
                stats.eval_calls * stats.n_snippets_fused, sum(s.cat_sizes),
                s.n_measures)
        return out


def _engine_config(cell: Cell, seed: int):
    import repro.verdict as vd

    return vd.EngineConfig(**cell.config["engine"], use_kernels=True,
                           seed=int(seed))


def _spawn_clients(spec: dict, workdir: str):
    path = os.path.join(workdir, "clients.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    return subprocess.Popen([sys.executable, str(CLIENTS), path],
                            stdout=subprocess.PIPE, text=True)


def _read_line(proc, prefix: str) -> str:
    for line in proc.stdout:
        if line.startswith(prefix):
            return line
    raise RuntimeError(f"client process ended before {prefix!r} "
                       f"(exit {proc.wait()})")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, require_tpu: bool = True,
             control: bool = False) -> dict:
    """One run; returns the result line's object.

    ``require_tpu=False`` skips the look for a chip and the persistent
    compilation cache (tests on the CPU). ``control=True`` also reads the
    control's numbers (calibration, never the benchmark's own runs).
    """
    t_start = time.monotonic() if t_start is None else t_start
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NothingToRun(f"needs {cell.chips} TPU chip(s); JAX found "
                           f"{len(devs)} {devs[0].platform} device(s)")
    if require_tpu:
        use_compile_cache()
    clock = CompileClock()
    sys.path.insert(0, str(ROOT / "src"))
    import repro.verdict as vd
    from repro.aqp.relation import Relation
    from repro.core.types import Schema
    from repro.serving.front import ServingFront, TenantSpec, serve_http
    from repro.serving.front.wire import query_from_json

    data = cell.config["data"]
    traffic = cell.traffic
    n_rows = int(data["rows"])
    layout = datagen.schema_arrays(data)
    schema = Schema(num_lo=layout["num_lo"], num_hi=layout["num_hi"],
                    cat_sizes=layout["cat_sizes"],
                    n_measures=len(layout["measure_names"]),
                    num_names=layout["num_names"],
                    cat_names=layout["cat_names"],
                    measure_names=layout["measure_names"])
    num, cat, meas = datagen.make_columns(data, seed, n_rows)
    rel = Relation.from_columns(schema, num, cat, meas)
    del num, cat, meas
    _log(f"setup: {n_rows} rows made at {time.monotonic() - t_start:.1f} s")
    cfg = _engine_config(cell, seed)
    budget = vd.ErrorBudget(**traffic["budget"])
    front = ServingFront(rel, cfg)
    tenant = front.add_tenant(TenantSpec("shared", rate=1e9, burst=10 ** 9,
                                         max_pending=10 ** 6, budget=budget))
    session = tenant.session
    _log(f"setup: front and sample at {time.monotonic() - t_start:.1f} s")

    warm_sizes = list(traffic["warmup_microbatches"])
    warm = queries.stream(data, traffic, seed, "warmup",
                          sum(warm_sizes) + int(traffic["warmup_requests"]))
    for chunk in _chunks(warm, warm_sizes):
        session.execute_many(
            [query_from_json(schema, q) for q in chunk], budget=budget)
        _log(f"setup: warm-up microbatch of {len(chunk)} at "
             f"{time.monotonic() - t_start:.1f} s")
    session.drain()

    capture = Capture(tenant.service, schema)
    n_window = int(traffic["max_answers_per_s"] * seconds) + 1000
    window = queries.stream(data, traffic, seed, "window", n_window)
    server = serve_http(front, "127.0.0.1", 0)
    workdir = tempfile.mkdtemp(prefix="chipbench-")
    try:
        proc = _spawn_clients({
            "port": server.server_address[1], "path": PATH,
            "clients": int(traffic["clients"]),
            "warmup": [{"query": q} for q in warm[sum(warm_sizes):]],
            "window": [{"query": q} for q in window],
            "seconds": seconds, "out": os.path.join(workdir, "window.json"),
        }, workdir)
        try:
            line = _read_line(proc, "WINDOW")
            start = float(line.split()[1])
            setup_s = start - t_start
            compiles_before = clock.count
            capture.scan_calls = capture.scan_bytes = 0
            traced = None
            if trace:
                traced = _trace_slice(start, seconds, workdir,
                                      traffic.get("trace_seconds", 5.0))
            _read_line(proc, "DONE")
        finally:
            proc.stdout.close()
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        compiles_in_window = clock.count - compiles_before
        with open(os.path.join(workdir, "window.json")) as f:
            rec = json.load(f)
        if traced is not None:
            traced = tracing.reduce(tracing.load(traced[0]), traced[1])
            top = sorted((traced or {}).get("programs", {}).items(),
                         key=lambda kv: -kv[1][0])[:12]
            _log("trace: programs by device seconds " + json.dumps(top))
    finally:
        server.shutdown()
        server.server_close()
        shutil.rmtree(workdir, ignore_errors=True)
    records = rec["records"]
    if rec["exhausted"]:
        raise RuntimeError("the window used every generated query; raise "
                           "the mix's max_answers_per_s")
    window_e2e = e2e.window_metrics(records, rec["start"], seconds)
    mem = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}

    # ---- what the check needs from the program, then free its state
    sample_idx = _check_sample(records, seed, int(traffic["check_sample"]))
    program = []
    from repro.aqp import queries as Q

    card = rel.cardinality
    for i in sample_idx:
        r = records[i]
        wire = window[r["index"]]
        ans = r["answer"]
        res = capture.results.get(query_from_json(schema, wire).build())
        raw = None
        if res is not None and not ans.get("served_from"):
            sa = res.snippet_answer
            raw = Q.assemble_results(res.plan, sa.raw_theta, sa.raw_beta2,
                                     card)
        program.append((wire, ans, raw))
    per_flush_bytes = (capture.scan_bytes / capture.scan_calls
                       if capture.scan_calls else None)
    front = tenant = session = capture = rel = None
    gc.collect()

    # ---- the reference
    t_ref = time.monotonic()
    limits = cell.config["limits"]
    cover_idx = (_draw(records, seed, "cover", int(traffic["cover_sample"]))
                 if "served_miss_share" in limits else [])
    # The control's served answers are its raw answers for the check
    # sample, so it reads its misses there.
    exact_idx = sorted(set(cover_idx) | (set(sample_idx) if control else set()))
    wanted = []
    for i in exact_idx:
        q = reference.Query(window[records[i]["index"]], layout)
        groups = {g for g, _ in reference.cells_by_key(
            records[i]["answer"]["cells"])}
        wanted.append((q, sorted(groups)))
    table, exact = reference.build_table(
        cell.config, layout, seed, cell.config["engine"], n_rows, wanted)
    exact = dict(zip(exact_idx, exact))
    z = reference.z_two_sided(budget.delta)
    readings = _compare(program, table, layout, card, "f64")
    del readings["served"]
    readings["served_miss_share"] = _miss_share(
        [(reference.cells_by_key(records[i]["answer"]["cells"]), exact[i])
         for i in cover_idx], z)
    readings["no_reply"] = float(window_e2e["no_reply"])
    ctl = None
    if control:
        ctl = _compare(program, table, layout, card, "bf16")
        ctl["served_miss_share"] = _miss_share(
            [(served, exact[i]) for i, served in
             zip(sample_idx, ctl.pop("served"))], z)
    ref_s = time.monotonic() - t_ref
    checks = {k: {"value": readings[k], "limit": limits[k]} for k in limits}
    # Nothing answered leaves nothing to hold against the reference.
    correct = bool(program) and all(c["value"] <= c["limit"]
                                    for c in checks.values())

    ctx = {
        "records": records, "window": window_e2e, "start": rec["start"],
        "seconds": seconds, "compiles_in_window": compiles_in_window,
        "trace": traced, "scan_bytes_per_call": per_flush_bytes,
        "device": device, "config": cell.config,
    }
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"], cell.metrics_dir)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if traced:
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
    else:
        values = dict(window_e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    out = {"correct": bool(correct), "attempted": window_e2e["attempted"],
           "failed": window_e2e["failed"], "metrics": metrics,
           "device": device}
    if trace and traced:
        out["breakdown"] = traced["breakdown"]
    if ctl is not None:
        out["control"] = ctl
    _log(f"window: {window_e2e['attempted']} sent, {window_e2e['failed']} "
         f"failed, {compiles_in_window} compiles; reference {ref_s:.1f} s "
         f"over {len(sample_idx)} answers; setup {setup_s:.1f} s")
    out["checks"] = checks
    return out


def _draw(records, seed: int, stream: str, size: int):
    """Indices of ``size`` answered requests drawn from the seed (all of
    them when fewer were answered)."""
    ok = [i for i, r in enumerate(records) if e2e.answered(r)]
    rng = np.random.default_rng([int(seed), queries.STREAMS[stream]])
    return sorted(rng.choice(ok, size=min(size, len(ok)),
                             replace=False).tolist()) if ok else []


def _check_sample(records, seed: int, size: int):
    """Indices of the answered requests whose raw answers are checked: a
    draw from the seed, with the request that used the most batches always
    in it."""
    ok = [i for i, r in enumerate(records) if e2e.answered(r)]
    if not ok:
        return []
    pick = set(_draw(records, seed, "sample", size))
    pick.add(max(ok, key=lambda i: records[i]["answer"]["batches_used"]))
    return sorted(pick)


def _miss_share(served, z: float) -> float:
    """Share of the cells, over ``(served cells, exact cells)`` pairs,
    whose exact answer lies outside the served interval."""
    misses = cells = 0
    for got, exact in served:
        m, n = reference.interval_misses(got, exact, z)
        misses, cells = misses + m, cells + n
    return misses / max(cells, 1)


def _compare(program, table, layout, card, precision) -> dict:
    """The numbers compared over the sampled answers. With
    ``precision="bf16"`` the control stands in the program's place."""
    est, bound, groups, raw_missing = [], [], 0, 0
    served_all = []
    for wire, ans, raw in program:
        q = reference.Query(wire, layout)
        want = reference.groups_of(q, table)
        served = reference.cells_by_key(ans["cells"])
        if {g for g, _ in served} != set(want):
            groups += 1
            served_all.append(served)
            continue
        ref = reference.reference_cells(q, table, ans["batches_used"], card,
                                        want)
        if precision != "f64":
            low = reference.reference_cells(q, table, ans["batches_used"],
                                            card, want, precision)
            raw = served = {k: (served[k][0], v[0], v[1])
                            for k, v in low.items()}
        elif raw is not None:
            raw = reference.cells_by_key(raw)
        if raw is not None:
            e, b = reference.scan_gaps(raw, ref)
            est.append(e)
            bound.append(b)
        elif not ans.get("served_from"):
            raw_missing += 1  # computed by the scan, yet never seen
        served_all.append(served)
    return {"groups_differ": float(groups),
            "raw_missing": float(raw_missing),
            "scan_estimate_gap": reference.worst(est),
            "scan_bound_gap": reference.worst(bound),
            "answers_checked": float(len(program)),
            "served": served_all}


def _trace_slice(start: float, seconds: float, workdir: str,
                 trace_seconds: float):
    """Profile a steady slice from a tenth into the window; returns the
    trace file and the traced seconds."""
    import jax

    lead = 0.1 * seconds
    length = min(float(trace_seconds), 0.8 * seconds)
    delay = start + lead - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    tdir = os.path.join(workdir, "trace")
    jax.profiler.start_trace(tdir)
    t0 = time.monotonic()
    time.sleep(length)
    window_s = time.monotonic() - t0  # stopping writes the trace: not traced
    jax.profiler.stop_trace()
    files = sorted(Path(tdir).rglob("*.xplane.pb"))
    return (files[-1], window_s) if files else None

