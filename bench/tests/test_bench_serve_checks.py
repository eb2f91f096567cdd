"""The serving cell's comparison at a size a CPU test run holds: a sound
run passes, a served token altered where the engine produces it makes
``correct`` false, and so does the control (the reference in float8 where
the configuration states bfloat16, reading the tokens it puts first).
The harness's look for a chip is skipped; everything else runs as on the
chip, with the kernels interpreted."""
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import reference  # noqa: E402
import serve_cell  # noqa: E402

SEED = 2**31 + 99


def tiny_cell():
    cell = harness.load_cell("qwen05b-chat")
    cell["config"] = dict(cell["config"], hidden_size=64,
                          intermediate_size=96, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          vocab_size=512)
    cell["mix"] = dict(cell["mix"], prompt_buckets=[8, 16, 32],
                       prompt_probs=[0.3, 0.4, 0.3], out_median=6,
                       out_min=2, out_max=12)
    cell["cell"] = dict(cell["cell"], slots=4, s_alloc=64, pool_blocks=16,
                        rate=2.0, first_wave=3, check_requests=3)
    return cell


def run(cell):
    return serve_cell.run(cell, SEED, 2.0, False, None,
                          harness.CompileClock(), time.monotonic(), {}, None)


def test_sound_run_is_correct():
    r = run(tiny_cell())
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "ttft_p90_ms", "itl_p95_ms"}


def test_closed_loop_keeps_every_slot_busy():
    cell = tiny_cell()
    cell["mix"] = dict(cell["mix"], arrivals="closed")
    cell["cell"] = dict(cell["cell"], max_requests=2000)
    r = run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= cell["cell"]["slots"] and r["failed"] == 0


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serve import engine

    def worst(req, sampled, row):
        if req.logits is not None:
            req.logits.append(row)
        return int(np.argmin(np.asarray(row)))

    monkeypatch.setattr(engine.Engine, "_chosen", staticmethod(worst))
    r = run(tiny_cell())
    assert not r["correct"], r["checks"]


def test_control_is_not_correct():
    cell = tiny_cell()
    srv = serve_cell.Server(cell, SEED, trace=False)
    srv.warm()
    srv.first_wave(cell["cell"]["first_wave"])
    srv.window(2.0, time.monotonic(), False)
    picked = serve_cell.sample_finished(srv, cell["cell"]["check_requests"])
    cases = [(srv.reqs[r].prompt, np.asarray(srv.reqs[r].output, np.int32))
             for r in picked]
    srv.free()
    gaps = serve_cell.reference_gaps(
        srv.dims, SEED, cases, cell["cell"]["s_alloc"],
        prec=reference.Precision(lower=True), ref_prec=reference.Precision())
    widest = max(float(g.max()) for g in gaps)
    assert widest > cell["cell"]["limits"]["served_logit_gap"], widest
