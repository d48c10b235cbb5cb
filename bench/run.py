"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics come from
``BENCHMARK.json`` and the files under ``bench/``.  The run sets up the
cell (weights or cluster from ``--seed``, every program it will run warmed
up), measures for ``--seconds``, frees the program's state, checks what the
timed path produced against the plain reference, and prints as the last
line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics with ``--trace 0``, the
per-layer ones with ``--trace 1``), ``device`` and, last, ``compared``.
The numbers compared also end standard error, each beside its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import core  # noqa: E402
from bench.spans import Spans, Tracer  # noqa: E402

EXIT_NO_CHIP = 3
EXIT_BAD_CELL = 2


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_chips(chips: int):
    """The devices this cell runs on; None where JAX finds no TPU or too
    few of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"JAX found no TPU (platform {devices[0].platform!r})")
        return None
    if len(devices) < chips:
        log(f"cell needs {chips} chips, JAX found {len(devices)}")
        return None
    return devices[:chips]


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def execute(cell: core.Cell, devices, args, runner_mod) -> dict:
    """Set up, measure, check.  Returns the result object to print."""
    from bench import tracefile

    spans = Spans()
    trace_dir = str(ROOT / ".bench_out" / "trace" / cell.name)
    tracer = Tracer(bool(args.trace), trace_dir)
    runner = runner_mod.Runner(cell, devices, args.seed, spans, log)
    runner.setup()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s")
    spans.reset()
    win = runner.window(args.seconds, tracer)
    tracer.stop()
    log(f"window {win.elapsed:.3f} s, {win.units} {win.unit_name}")
    peak = memory_peak(devices)
    runner.release()
    compared, attempted, failed = runner.check()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": all(c.ok for c in compared) and failed == 0,
              "attempted": attempted, "failed": failed}
    if args.trace:
        summary = tracefile.summarize_file(tracefile.find_xplane(trace_dir),
                                           len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        view = core.RunView(
            cell=cell, chips=len(devices), peaks=core.load_peaks(d0.device_kind),
            window_s=win.elapsed, units=win.units, e2e=win.e2e,
            counters=dict(spans.counters), spans=dict(spans.total),
            trace=summary, traced_units=win.traced_units, extra=win.extra,
        )
        result["metrics"] = core.per_layer_values(view)
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = summary.breakdown()
    else:
        e2e = dict(win.e2e, setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in e2e
        }
    result["device"] = device
    result["compared"] = {c.name: c.as_json() for c in compared}
    return result, compared


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = core.load_cell(args.workload)
        runner_mod = core.load_runner(cell)
    except core.CellError as e:
        log(str(e))
        return EXIT_BAD_CELL
    devices = check_chips(cell.chips)
    if devices is None:
        return EXIT_NO_CHIP
    from repro.launch.compile_cache import enable_compile_cache

    import jax

    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result, compared = execute(cell, devices, args, runner_mod)
    sys.stdout.flush()
    for c in compared:
        print(f"[compared] {c.line()}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
