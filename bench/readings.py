"""The readings that a cell's limits are set from, for several seeds in
one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 [--seconds s]

Training cells: for each seed, the program's first three steps and the
plain reference's, compared (the lower reading); the control, which is the
reference computed with float8 where the configuration states bfloat16;
and the fault of half of the batch left out, planted in the reference put
in the program's place.  (A step that returns its state unchanged reads 1
on ``change_gap`` by the measure itself.)

Serving cells: for each seed, the engine with that seed's weights serves
the cell's first wave and ``--seconds`` of its traffic; the finished
requests that a run would check are compared with the reference (the
lower reading) and with the control: at each position of the same prompts
and served tokens, the gap of the token that the float8 reference puts
first.

Prints one JSON line per seed.  Run it on the chip at the cell's size.
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


def train_readings(cell: dict, seed: int) -> dict:
    import numpy as np

    import reference
    import traffic
    import train_cell
    import weights

    wl, mix = cell["cell"], cell["mix"]
    dims = weights.dims_of(cell["config"])
    prog = train_cell.Program(cell, seed)
    got = prog.first_steps()
    prog.free()
    args = (dims, wl["lr"], mix, wl["batch"], seed)
    ref = train_cell.reference_readings(*args, reference.Precision())

    def half(i):
        b = traffic.train_batch(mix, dims["vocab_size"], wl["batch"], seed, i)
        m = b["mask"].reshape(-1).copy()
        m[m.size // 2:] = 0.0
        return dict(b, mask=m.reshape(b["mask"].shape))

    out = {"program": train_cell.compare(got, ref),
           "control": train_cell.compare(train_cell.reference_readings(
               *args, reference.Precision(lower=True)), ref),
           "half_batch": train_cell.compare(train_cell.reference_readings(
               *args, reference.Precision(), batch=half), ref),
           "loss": got["loss"], "loss_ref": ref["loss"]}
    names = train_cell.unit_names(weights.build(dims, seed))
    gr, gp = np.asarray(ref["grad"]), np.asarray(got["grad"])
    worst = int(np.argmax(np.abs(gp - gr) / np.maximum(gr, np.median(gr))))
    out["worst_grad_unit"] = names[worst]
    return out


def serve_readings(srv, cell: dict, seed: int, seconds: float) -> dict:
    import jax
    import numpy as np

    import reference
    import serve_cell
    import sweep

    wl = cell["cell"]
    for x in jax.tree.leaves(srv.eng.params):
        x.delete()
    srv.eng.params = srv.packed_weights(seed)
    srv.seed = seed
    srv.reqs.clear(), srv.stamps.clear(), srv.due.clear()
    srv.first_wave(wl["first_wave"])
    srv.window(seconds, time.monotonic(), False)
    picked = serve_cell.sample_finished(srv, wl["check_requests"])
    cases = [(srv.reqs[r].prompt, np.asarray(srv.reqs[r].output, np.int32))
             for r in picked]
    sweep.empty(srv)
    args = (srv.dims, seed, cases, wl["s_alloc"])
    gaps = {"program": serve_cell.reference_gaps(*args),
            "control": serve_cell.reference_gaps(
                *args, prec=reference.Precision(lower=True),
                ref_prec=reference.Precision())}
    return {k: [round(float(g.max()), 4) for g in v]
            for k, v in gaps.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.require_chip(cell["entry"]["chips"])
    except harness.NoChip as e:
        return e.code
    harness.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    srv = None
    if cell["mix"]["kind"] != "train":
        import serve_cell

        srv = serve_cell.Server(cell, seeds[0], trace=False)
        srv.warm()
    for seed in seeds:
        t = time.monotonic()
        out = (train_readings(cell, seed) if srv is None
               else serve_readings(srv, cell, seed, args.seconds))
        print(json.dumps(dict(seed=seed, seconds=time.monotonic() - t,
                              **out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
