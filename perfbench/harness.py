"""The benchmark harness: finds a cell's files by name, sets it up, drives
its window, reads its metrics and decides `correct`.

Everything a cell is made of is found through BENCHMARK.json:

- the configuration: the file its `configs` entry names;
- the traffic mix: perfbench/traffic/<traffic>.json, read by generate.py;
  its "driver" names the loop that drives the window,
  perfbench/drivers/<driver>.py, which declares `PER_SET` (whether its
  answers are per-set verdicts or one per request), `buckets(stream)` (the
  (S, K) buckets it dispatches, warmed at set-up) and `drive(ctx)`;
- each metric, end-to-end or per-layer: perfbench/metrics/<name>.py, or,
  for a name with a suffix (`stage_ms_per_batch.gossip`),
  perfbench/metrics/<base>.py; its `read(m)` returns a number or None
  (nothing to read: a per-layer metric is left out, an end-to-end one is an
  error). `m` holds the driver's result, the program's counter deltas over
  the window, the reduced trace and the set-up time.

A cell, configuration, traffic mix or metric is added by new files and new
BENCHMARK.json entries; no file here changes.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import resource
import shutil
import sys
import time
from types import SimpleNamespace

from . import check as checker
from . import generate, keys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"
TRACE_DIR = ROOT / ".perfbench_trace"
ANSWER_TIMEOUT = 60.0  # seconds a late answer is waited for after the window
# A chip trace stopped at the window's close can lack the device's last
# 0.1-0.3 s before it (an idle gap that untraced rates show is not there), so
# tracing goes on this long after the close; the reduction clips to the
# window. Kept short: on a v5e the profiler takes about 210 s to stop after
# 3.8 s of this verifier's device events.
TRACE_TAIL_S = 0.5


def log(msg: str) -> None:
    print(f"perfbench: {msg}", flush=True)


def load_benchmark(path=BENCHMARK) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(bench: dict, workload: str) -> SimpleNamespace:
    cell = _by_name(bench["workloads"], workload, "workload")
    config_entry = _by_name(bench["configs"], cell["config"], "config")
    config = json.loads((ROOT / config_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return SimpleNamespace(
        name=workload,
        chips=cell["chips"],
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])],
        per_layer=[m for m in bench["per_layer"] if workload in m.get("workloads", [workload])],
    )


def _load_file(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(f"perfbench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str):
    return _load_file(HERE / "drivers" / f"{name}.py")


def load_reader(name: str):
    exact = HERE / "metrics" / f"{name}.py"
    return _load_file(exact if exact.exists() else HERE / "metrics" / f"{name.split('.')[0]}.py")


def program_counters() -> dict:
    """The program's own counters that per-layer metrics read."""
    from lighthouse_tpu.common.metrics import (
        BLS_BATCH_PADDED_SIZE,
        BLS_BISECTION_DISPATCHES_TOTAL,
        BLS_COALESCED_BATCH_SIZE,
        BLS_JIT_BUILDS_TOTAL,
        BLS_STAGE_SECONDS,
    )

    return {
        "stage_seconds": BLS_STAGE_SECONDS.sum,
        "stage_count": BLS_STAGE_SECONDS.count,
        "padded_rows": BLS_BATCH_PADDED_SIZE.sum,
        "padded_batches": BLS_BATCH_PADDED_SIZE.count,
        "coalesced_sets": BLS_COALESCED_BATCH_SIZE.sum,
        "coalesced_batches": BLS_COALESCED_BATCH_SIZE.count,
        "jit_builds": BLS_JIT_BUILDS_TOTAL.labels(kernel="verify").value,
        "bisection_dispatches": BLS_BISECTION_DISPATCHES_TOTAL.value,
    }


class Inputs:
    """The program's own objects for the stream: validator public keys made
    once (with their limb rows precomputed, as the node's pubkey cache does
    on admission) and, for every use of a request, fresh signature objects,
    as decompression makes them in the node."""

    def __init__(self, bls, stream, msgs, pk_xy):
        from lighthouse_tpu.crypto.bls.ref.curves import _B1, _B2, Point
        from lighthouse_tpu.crypto.bls.ref.fields import Fp, Fp2

        self.bls, self.stream, self.msgs = bls, stream, msgs
        self._g2 = lambda c: Point(Fp2.from_ints(c[0], c[1]), Fp2.from_ints(c[2], c[3]), False, _B2)
        pks = [bls.PublicKey(Point(Fp(x), Fp(y), False, _B1)) for x, y in pk_xy]
        precompute = getattr(bls, "precompute_pubkey_limbs", None)
        if precompute is not None:
            for pk in pks:
                precompute(pk)
        self.key_lists = [[[pks[v] for v in s.keys] for s in req] for req in stream.base]

    def make_sets(self, i: int) -> list:
        bls = self.bls
        lists = self.key_lists[i % len(self.key_lists)]
        return [
            bls.SignatureSet(bls.Signature(self._g2(s.sig)), lists[j], self.msgs[s.msg])
            for j, s in enumerate(self.stream.request(i))
        ]


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float, *,
             bls=None, devices=None, control=None, split=None) -> dict:
    """One run of a cell: set-up, window, metrics, check. Returns the
    result line's object; earlier lines go to stdout as it runs. `split`
    holds set-up times measured before the call (the JAX import)."""
    split = dict(split or {})
    t = time.perf_counter()
    if bls is None:
        from lighthouse_tpu.crypto import bls as bls_pkg

        bls = bls_pkg.backend("jax")
    split["program_import_s"] = time.perf_counter() - t

    t = time.perf_counter()
    traffic = cell.traffic
    pool = generate.load_pool()
    stream = generate.generate(cell.config, traffic, seed, pool)
    pk_xy = keys.g1_chain(1, stream.validators)
    inputs = Inputs(bls, stream, pool[0], pk_xy)
    split["data_s"] = time.perf_counter() - t

    driver = load_driver(traffic["driver"])
    buckets = None
    if hasattr(bls, "compile_verify_kernels"):  # the jax backend; tests run the plain one
        buckets = driver.buckets(stream)
        t = time.perf_counter()
        bls.compile_verify_kernels(buckets)
        split["program_load_s"] = time.perf_counter() - t

    marks = {}
    window_seconds = min(seconds, traffic["trace_seconds"]) if trace else seconds

    def window_opens():
        marks.update(open=time.perf_counter(), c0=program_counters())
        if trace:  # the trace holds the window alone: a chip trace is large
            import jax

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # every Python call would be an event
            opts.host_tracer_level = 1  # the harness's spans, not the runtime's
            opts.enable_hlo_proto = False  # the six programs' HLO, not read here
            t = time.perf_counter()
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            marks["trace_start_s"] = time.perf_counter() - t

    def window_closes():
        marks.update(close=time.perf_counter(), c1=program_counters())
        if trace:
            import jax

            time.sleep(TRACE_TAIL_S)
            t = time.perf_counter()
            jax.profiler.stop_trace()
            marks["trace_stop_s"] = time.perf_counter() - t

    ctx = SimpleNamespace(
        bls=bls,
        traffic=traffic,
        seconds=window_seconds,
        answer_timeout=ANSWER_TIMEOUT,
        make_sets=inputs.make_sets,
        annotate=_annotate if devices is not None else (lambda name: contextlib.nullcontext()),
        window_opens=window_opens,
        window_closes=window_closes,
    )
    t = time.perf_counter()
    result = driver.drive(ctx)
    split["warmup_s"] = marks["open"] - t
    setup_s = marks["open"] - t_start
    reduced = {}
    if trace:
        from . import trace as tracemod

        files = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        t = time.perf_counter()
        reduced = tracemod.reduce_file(files[-1]) if files else {}
        log(f"trace: {len(files)} file(s), {sum(f.stat().st_size for f in files)} bytes, started in "
            f"{marks['trace_start_s']:.1f}s, stopped in {marks['trace_stop_s']:.1f}s, "
            f"reduced in {time.perf_counter() - t:.1f}s: "
            + json.dumps({k: reduced[k] for k in ("window_s", "busy_s", "idle_share", "stage_s",
                                                   "stage_runs", "device_span") if k in reduced}))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    device = {}
    if devices is not None:
        used = devices[: cell.chips]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used]
        device = {"platform": used[0].platform, "kind": used[0].device_kind,
                  "count": len(used), "memory_peak_bytes": max(peaks)}
        if trace and reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    del inputs  # the program's objects go before the reference runs

    c0, c1 = marks["c0"], marks["c1"]
    deltas = {k: c1[k] - c0[k] for k in c0}
    log("setup split: " + json.dumps({**split, "setup_s": setup_s}))
    log(f"host peak RSS {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024} bytes")
    log(f"stage builds inside the window: {deltas['jit_builds']:g} (expected 0)")
    log(f"bisection dispatches: {c0['bisection_dispatches']:g} before the window, "
        f"{deltas['bisection_dispatches']:g} in it")
    if buckets is not None:
        log(f"buckets compiled {buckets}; dispatched {sorted(getattr(bls, '_chains', {}))}")
    log(f"window: {result['units']} {traffic['unit']} in {result['t_end'] - result['t0']:.6f}s; "
        f"{result['submitted']} requests submitted in all; batches staged in window "
        f"{deltas['padded_batches']}, padded rows {deltas['padded_rows']:g}")
    log(f"driver {traffic['driver']}: {json.dumps(result.get('notes', {}))}")

    t = time.perf_counter()
    numbers, control_numbers, notes = checker.check(
        stream, result, pool[0], pk_xy, traffic["check_sets"], seed, driver.PER_SET, control)
    log(f"check: {json.dumps(notes)}; reference {time.perf_counter() - t:.1f}s")
    if control_numbers is not None:
        log(f"check of the program itself: {json.dumps(numbers)}; control {control} in its place")
        numbers = control_numbers
    correct = all(numbers[k] <= checker.LIMITS[k] for k in checker.LIMITS)
    attempted = result["submitted"]
    failed = numbers["missing"] + numbers["wrong"]

    metrics = {}
    m = SimpleNamespace(result=result, counters=deltas, trace=reduced, setup_s=setup_s)
    for spec in cell.per_layer if trace else cell.end_to_end:
        value = load_reader(spec["name"]).read(m)
        if value is None and not trace:
            raise KeyError(f"cell {cell.name} has no value for {spec['name']}")
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}

    checks = {k: {"value": numbers[k], "limit": checker.LIMITS[k]} for k in checker.LIMITS}
    for k, v in checks.items():
        print(f"perfbench: check {k} = {v['value']} (limit {v['limit']})", file=sys.stderr)
    print(f"perfbench: correct = {correct}", file=sys.stderr, flush=True)
    line = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": metrics,
            "device": device}
    if trace and reduced:
        line["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = checks
    return line
