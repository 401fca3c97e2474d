"""Reduce a profiler trace (.xplane.pb) to the benchmark's device numbers.

Read with `jax.profiler.ProfileData`. The traced window is the host span
named "window" that the harness opens and closes; everything is clipped to
it. On each device plane (/device:TPU:n):

- busy time is the union of the intervals of the "XLA Ops" line (the
  "XLA Modules" line where a trace has no op line), and the idle share is
  1 - busy / window;
- device time per stage program sums the "XLA Modules" events, each named
  by the program's jit name and mapped to a stage by the files in
  perfbench/stages/ (a substring match); the rest is "other";
- whole batches: runs of module events that start with the first stage of
  a chain (`FIRST`), end with its last (`LAST`), hold each stage once and
  lie wholly inside the window, each with its stage times; a batch cut by
  an edge of the window is not among them;
- the longest idle gaps are labelled by the harness span (submit, wait,
  prepare, verify, ...) that overlaps each gap most, or "none".

Busy time and stage times are averaged over the device planes.
"""

from __future__ import annotations

import json
import pathlib

STAGES_DIR = pathlib.Path(__file__).resolve().parent / "stages"
HOST_SPANS = ("submit", "wait", "prepare", "verify")
FIRST, LAST = "unpack", "final"


def stage_patterns(directory=STAGES_DIR) -> list[tuple[str, str]]:
    """(substring, stage) pairs from every stage file."""
    out = []
    for f in sorted(pathlib.Path(directory).glob("*.json")):
        spec = json.loads(f.read_text())
        out += [(m, spec["stage"]) for m in spec["match"]]
    return out


def stage_of(name: str, patterns) -> str:
    for sub, stage in patterns:
        if sub in name:
            return stage
    return "other"


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def whole_batches(modules, lo, hi) -> list[dict[str, float]]:
    """Stage seconds of each whole batch among (stage, start, end) module
    events in time order (module docstring)."""
    out, cur = [], None
    for stage, a, b in modules:
        if stage == FIRST:
            cur = {} if a >= lo else None
        if cur is None:
            continue
        if stage in cur:
            cur = None
            continue
        cur[stage] = (b - a) / 1e9
        if stage == LAST:
            if b <= hi:
                out.append(cur)
            cur = None
    return out


def reduce(pd, patterns=None, top: int = 10) -> dict:
    """The device numbers of one trace (a ProfileData)."""
    patterns = stage_patterns() if patterns is None else patterns
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [ev for ev in _events(line) if ev[0] in HOST_SPANS or ev[0] == "window"]
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if "XLA Modules" in lines:
                ops = lines.get("XLA Ops") or lines["XLA Modules"]  # intervals alone: ops are many
                devices.append((_events(lines["XLA Modules"]),
                                [(e.start_ns, e.start_ns + e.duration_ns) for e in ops.events]))
    windows = [(a, b) for name, a, b in host if name == "window"]
    if not windows or not devices:
        return {}
    lo, hi = windows[0]
    window_s = (hi - lo) / 1e9
    busy = 0.0
    stage_s: dict[str, float] = {}
    runs: dict[str, int] = {}
    gaps, batches, first, last = [], [], hi, lo
    for module_events, ops in devices:
        spans = _union(_clip(a, b, lo, hi) for a, b in ops if b > lo and a < hi)
        if spans:
            first, last = min(first, spans[0][0]), max(last, spans[-1][1])
        busy += sum(b - a for a, b in spans) / 1e9
        edges = [lo] + [t for ab in spans for t in ab] + [hi]
        gaps += [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2) if edges[k + 1] > edges[k]]
        for name, a, b in module_events:
            if b <= lo or a >= hi:
                continue
            stage = stage_of(name, patterns)
            ca, cb = _clip(a, b, lo, hi)
            stage_s[stage] = stage_s.get(stage, 0.0) + (cb - ca) / 1e9
            if lo <= a:
                runs[stage] = runs.get(stage, 0) + 1
        modules = sorted(((stage_of(name, patterns), a, b) for name, a, b in module_events),
                         key=lambda m: m[1])
        batches += whole_batches(modules, lo, hi)
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for a, b in gaps[:top]:
        best, label = 0, "none"
        for name, ha, hb in host:
            if name == "window":
                continue
            overlap = min(b, hb) - max(a, ha)
            if overlap > best:
                best, label = overlap, name
        labelled.append([label, (b - a) / 1e9])
    stage_s = {k: v / n for k, v in stage_s.items()}
    return {
        "devices": n,
        "window_s": window_s,
        "busy_s": busy / n,
        "idle_share": 1.0 - busy / n / window_s,
        "stage_s": stage_s,
        "stage_runs": {k: v // n for k, v in runs.items()},
        "batches": batches,
        "device_span": [(first - lo) / 1e9, (hi - last) / 1e9],
        "device_ops": sorted(([k, v] for k, v in stage_s.items()), key=lambda kv: -kv[1])[:top],
        "idle_gaps": labelled,
    }


def reduce_file(path) -> dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(str(path)))
