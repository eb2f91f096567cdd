"""On-chip benchmark of NVFP4 serving and the QAD step.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process on the chip(s) it
finds: makes the weights and the traffic from the seed, warms up exactly
the cell's shapes (set-up), measures for ``--seconds``, then checks what
the timed path produced against the plain reference (``reference.py``).
Prints the compared numbers beside their limits as the last lines of
stderr and the result as the last line of stdout: the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics (from a profiler trace of
the window) with ``--trace 1``.  Exits nonzero, printing no result, where
JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_chip(cell["entry"]["chips"])
    except harness.NoChip as e:
        return e.code
    cache = harness.enable_compile_cache()
    clock = harness.CompileClock()
    import peaks

    dev = harness.device_info(devices)
    print(f"[bench] workload={args.workload} seed={args.seed} device={dev} "
          f"compile_cache={cache}", file=sys.stderr, flush=True)
    readers = ({m["name"]: harness.load_metric(m["name"])
                for m in cell["per_layer"]} if args.trace else {})
    kind = cell["mix"]["kind"]
    if kind == "train":
        import train_cell as driver
    else:
        import serve_cell as driver
    result = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                        devices, clock, T_START, readers,
                        peaks.peaks(dev["kind"]))
    dev["memory_peak_bytes"] = result["memory_peak_bytes"]
    if args.trace:
        dev["busy_s"], dev["window_s"] = result["busy_s"], result["window_s"]
    result["device"] = dev
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
