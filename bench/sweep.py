"""Find the knee of an open-loop serving cell: the highest Poisson rate at
which the backlog does not grow.

    python3 bench/sweep.py --workload <cell> --seeds 1,2,3 --seconds <s> \\
        --rates 0.2,0.4,0.6 [--draw-sizes]

One process builds the cell's engine once, and for each seed and each
rate seats the cell's first wave, serves the mix open loop for
``--seconds`` and prints one line: the backlog (requests due and still
without a first token) at each quarter of the window, tokens per second,
and the p90 time to first token.  With ``--draw-sizes`` each seed also
draws the mix's lengths and arrival gaps (it stands in for the mix's
``sizes_seed``), so the knee is found on several draws of the mix and not
one.  Between runs the requests in flight are cut short and the engine is
emptied.  The cell's fixed rate is then set, as a number in its workload
file, at four fifths of the lowest knee: the highest rate at which, on
every draw, the backlog does not grow and the p90 time to first token
stays within twice its value at the lowest rate tried.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402


def backlog(srv, win, t: float) -> int:
    """Requests due by ``t`` (seconds into the window) without a first
    token by then."""
    at = win["t0"] + t
    return int(sum(srv.due[r] <= t
                   and (not srv.stamps[r] or srv.stamps[r][0] > at)
                   for r in win["in_window"]))


def empty(srv) -> None:
    """Cut every request in flight short and drop the queue."""
    sched = srv.eng.sched
    sched.waiting.clear()
    for r in sched.in_flight():
        r.max_new_tokens = len(r.output) + 1
    srv.eng.drain(max_steps=10_000)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--draw-sizes", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.require_chip(cell["entry"]["chips"])
    except harness.NoChip as e:
        return e.code
    harness.enable_compile_cache()
    import numpy as np

    import serve_cell

    seeds = [int(x) for x in args.seeds.split(",")]
    mix = cell["mix"]
    srv = serve_cell.Server(cell, seeds[0], trace=False)
    srv.warm()
    for seed in seeds:
        srv.seed = seed
        if args.draw_sizes:
            srv.mix = dict(mix, sizes_seed=seed)
        for rate in [float(r) for r in args.rates.split(",")]:
            srv.first_wave(cell["cell"]["first_wave"])
            win = srv.window(args.seconds, time.monotonic(), False,
                             rate=rate, drain=False)
            lat = serve_cell.latencies(srv, win)
            q = [backlog(srv, win, args.seconds * f)
                 for f in (.25, .5, .75, 1)]
            print(json.dumps({
                "seed": seed, "sizes_seed": srv.mix["sizes_seed"],
                "rate": rate, "due": lat["attempted"],
                "backlog_quarters": q,
                "out_tok_s": lat["tokens"] / lat["seconds"],
                "ttft_p90_ms": 1e3 * float(np.percentile(lat["ttft"], 90))
                if lat["ttft"] else None,
                "itl_p95_ms": 1e3 * float(np.percentile(lat["itl"], 95))
                if lat["itl"] else None}), flush=True)
            empty(srv)
            srv.reqs.clear()
            srv.stamps.clear()
            srv.due.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
