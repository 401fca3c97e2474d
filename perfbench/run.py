"""Run one cell of the benchmark on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints progress and the set-up split on earlier lines, the numbers that
decide `correct` (each with its limit) as the last lines of standard
error, and one JSON object as the last line of standard output. Exits
non-zero and prints no result when JAX finds no TPU, or fewer chips than
the cell asks for.

`--control <name>` puts a control (perfbench/ref/verify.py) in the
program's place for the check, which must then come out not correct; the
benchmark's own runs never pass it. `--seed` takes several seeds to read
the check on each in one process, with the set-up paid once: a result line
for each, and only the first one's `setup_s` is the process's.

JAX's persistent compilation cache is kept at <checkout>/.jax_cache, so
only the first run of a cell in a checkout compiles.
"""

from __future__ import annotations

import time


def _process_start() -> float:
    """perf_counter() at the moment this process started (Linux), else now."""
    now = time.perf_counter()
    try:
        import os

        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


T_START = _process_start()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)  # the checkout, not perfbench/ (whose trace.py is not the stdlib's)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)

    from perfbench import harness

    cell = harness.load_cell(harness.load_benchmark(), args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    t = time.perf_counter()
    import jax

    jax_import_s = time.perf_counter() - t
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"perfbench: no accelerator: the cell needs {cell.chips} TPU chip(s), jax found "
              f"{len(devices)} {devices[0].platform!r} device(s)", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.log(f"device: {devices[0].device_kind} x{len(devices)}; jax import {jax_import_s:.3f}s; "
                f"cache {jax.config.jax_compilation_cache_dir}")
    t_start, split = T_START, {"jax_import_s": jax_import_s}
    for seed in args.seed:
        line = harness.run_cell(cell, seed, args.seconds, bool(args.trace), t_start,
                                devices=devices, control=args.control, split=split)
        harness.log(f"seed {seed} done {time.perf_counter() - T_START:.1f}s after the process started")
        print(json.dumps(line), flush=True)
        t_start, split = time.perf_counter(), {}
    return 0


if __name__ == "__main__":
    sys.exit(main())
