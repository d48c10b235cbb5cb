"""Readings from which the correctness limits are set, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed, in one process: the cell's set-up and a short window as a
run makes them, then the runner's ``calibration()``: the numbers the check
compares, read for the program, for the control (the reference in the
precision below the configuration's, in the program's place) and for the
faults the runner plants.  Each set of readings is held to the cell's
limits as a run's check holds the program's (``Runner.compare``), and its
verdict is printed beside it: one JSON line per seed on standard output.
The exit code is 1 where a reading named in the runner's ``MUST_PASS``
comes out not correct, or another (the control, a fault) comes out
correct, on any seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import core  # noqa: E402
from bench.run import check_chips, log  # noqa: E402
from bench.spans import Spans, Tracer  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = core.load_cell(args.workload)
    devices = check_chips(cell.chips)
    if devices is None:
        return 3
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    mod = core.load_runner(cell)
    unexpected = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        runner = mod.Runner(cell, devices, seed, Spans(), log)
        runner.setup()
        runner.window(args.seconds, Tracer(False, ""))
        runner.release()
        cal = runner.calibration()
        correct = {}
        for name, readings in cal.items():
            compared = runner.compare(readings)
            correct[name] = all(c.ok for c in compared)
            for c in compared:
                log(f"seed {seed} {name}: {c.line()}")
            if correct[name] != (name in mod.MUST_PASS):
                unexpected.append((seed, name))
        out = {"seed": seed, **cal, "correct": correct,
               "seconds": time.perf_counter() - t0}
        print(json.dumps(out, default=float), flush=True)
        del runner
    for seed, name in unexpected:
        log(f"seed {seed}: {name} came out "
            f"{'not ' if name in mod.MUST_PASS else ''}correct")
    return 1 if unexpected else 0


if __name__ == "__main__":
    sys.exit(main())
