"""The QAD cell's comparison at a size a CPU test run holds: sound runs
pass, and the control (the reference in float8 where the configuration
states bfloat16) and each fault the cell can have make ``correct`` false.

A fault is planted underneath the timed path: the program's step is
wrapped so that it returns its state unchanged, or leaves out half of the
batch's tokens and takes the mean over the rest.  The harness's look for
a chip is skipped; everything else runs as on the chip.
"""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import reference  # noqa: E402
import train_cell  # noqa: E402
import weights  # noqa: E402

SEED = 2**31 + 12345


def tiny_cell():
    cell = harness.load_cell("qwen05b-qad")
    cell["config"] = dict(cell["config"], hidden_size=64,
                          intermediate_size=96, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          vocab_size=512)
    cell["mix"] = dict(cell["mix"], seq_len=64)
    cell["cell"] = dict(cell["cell"], batch=2)
    return cell


def run(cell):
    return train_cell.run(cell, SEED, 0.5, False, None,
                          harness.CompileClock(), time.monotonic(), {}, None)


def broken_step(monkeypatch, fault):
    from repro.core import qad

    make = qad.make_train_step
    # the compiled step is cached per configuration: build it anew here,
    # and again for whatever test runs next
    train_cell._program_step.cache_clear()
    monkeypatch.setattr(train_cell, "_program_step",
                        train_cell.functools.lru_cache()(
                            train_cell._program_step.__wrapped__))

    def make_broken(*a, **kw):
        step = make(*a, **kw)

        def broken(state, batch):
            if fault == "unchanged":
                _, metrics = step(state, batch)
                return state, metrics
            mask = batch["mask"].reshape(-1)
            mask = mask.at[mask.shape[0] // 2:].set(0.0)
            return step(state, dict(batch,
                                    mask=mask.reshape(batch["mask"].shape)))
        return broken

    monkeypatch.setattr(qad, "make_train_step", make_broken)


def test_sound_run_is_correct():
    r = run(tiny_cell())
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "train_tok_s"}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_not_correct(monkeypatch, fault):
    broken_step(monkeypatch, fault)
    r = run(tiny_cell())
    assert not r["correct"], r["checks"]


def test_control_is_not_correct():
    cell = tiny_cell()
    dims, wl = weights.dims_of(cell["config"]), cell["cell"]
    ref = train_cell.reference_readings(
        dims, wl["lr"], cell["mix"], wl["batch"], SEED, reference.Precision())
    ctl = train_cell.reference_readings(
        dims, wl["lr"], cell["mix"], wl["batch"], SEED,
        reference.Precision(lower=True))
    gaps = train_cell.compare(ctl, ref)
    assert any(gaps[k] > v for k, v in wl["limits"].items()), gaps


def test_reference_nvfp4_matches_the_format():
    """E2M1 rounding ties to the even code; an E4M3 block scale and the
    tensor scale amax / (448 * 6) reproduce a block that is on the grid."""
    a = jnp.asarray([0.25, 0.75, 1.75, 2.5, 3.5, 5.0, 7.0])
    assert reference._e2m1(a).tolist() == [0.0, 1.0, 2.0, 2.0, 4.0, 4.0, 6.0]
    grid = jnp.asarray([0, .5, 1, 1.5, 2, 3, 4, 6] * 2, jnp.float32)
    x = jnp.concatenate([grid, -grid])[None, :]
    amax = jnp.max(jnp.abs(x))
    assert jnp.array_equal(reference.nvfp4_qdq(x, amax), x)
