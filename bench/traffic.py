"""The one traffic generator: every mix is a data file under ``traffic/``.

A mix file is JSON with a ``kind``:

  * ``train`` — training rows: ``seq_len`` tokens per row drawn uniformly
    from the vocabulary (``batch`` rows per step, set by the cell).  Every
    row of every step differs; step i's batch is a pure function of
    (seed, i), made on the host as a loader would.
  * ``serve`` — requests: ``prompt_buckets`` (lengths) with
    ``prompt_probs``; output lengths lognormal (``out_median``,
    ``out_sigma``) clipped to [``out_min``, ``out_max``]; ``arrivals`` is
    ``poisson`` (open loop, at the cell's rate) or ``closed`` (one request
    per client, the next when it finishes).  Prompt tokens are uniform over
    the vocabulary.  The lengths come from the mix's own ``sizes_seed``, so
    every run does the same work; the run's seed orders them, places the
    arrivals and picks the tokens.

Everything is drawn from ``numpy.random.Generator(PCG64)``, so the same
seed gives the same traffic.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64([int(seed), *stream]))


def train_batch(mix: dict, vocab: int, rows: int, seed: int,
                step: int) -> dict:
    """Step ``step``'s batch: tokens, next-token labels, and a mask."""
    s = mix["seq_len"]
    t = _rng(seed, 1, step).integers(0, vocab, (rows, s + 1), np.int32)
    return {"tokens": t[:, :-1], "labels": t[:, 1:],
            "mask": np.ones((rows, s), np.float32)}


def _sizes(mix: dict, n: int, stream: int) -> tuple:
    """``n`` (prompt length, output length) pairs drawn from the mix with
    its own ``sizes_seed``: the same for every run's seed."""
    rng = _rng(mix["sizes_seed"], stream, n)
    lens = rng.choice(np.asarray(mix["prompt_buckets"]), size=n,
                      p=np.asarray(mix["prompt_probs"]))
    out = np.exp(rng.normal(np.log(mix["out_median"]), mix["out_sigma"], n))
    out = np.clip(np.rint(out), mix["out_min"], mix["out_max"]).astype(int)
    return lens, out


def requests(mix: dict, vocab: int, seed: int, n: int) -> list[dict]:
    """``n`` requests: {"prompt": int32 [P], "max_new": int}.  Every seed
    gets the same lengths, in an order and with prompt tokens of its own."""
    lens, out = _sizes(mix, n, 2)
    rng = _rng(seed, 2)
    order = rng.permutation(n)
    return [{"prompt": rng.integers(0, vocab, int(lens[i]), np.int32),
             "max_new": int(out[i])} for i in order]


def arrivals(mix: dict, rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times of an open-loop window: Poisson arrivals at ``rate`` per
    second over ``seconds``, held to their expected count (a Poisson
    process given its count: the n + 1 gaps around n arrivals are
    exponential, scaled to fill the window).  The gaps come from the mix's
    ``sizes_seed``, so every seed gets the same gaps, in its own order."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"arrivals {mix['arrivals']!r} has no schedule")
    n = max(1, int(round(rate * seconds)))
    gaps = _rng(mix["sizes_seed"], 3, n).exponential(1.0, n + 1)
    gaps = gaps[_rng(seed, 3).permutation(n + 1)]
    return np.cumsum(gaps)[:n] * (seconds / gaps.sum())


def first_wave(mix: dict, vocab: int, seed: int, n: int) -> list[dict]:
    """``n`` requests as they stand in a steady state: each drawn in
    proportion to its output length (a request in flight is more likely a
    long one), with a uniform share of that output left to serve.  The
    lengths are the same for every seed."""
    lens, out = _sizes(mix, 64 * max(n, 1), 6)
    rng = _rng(mix["sizes_seed"], 7, n)
    pick = rng.choice(len(out), size=n, replace=False, p=out / out.sum())
    left = [int(rng.integers(1, out[i] + 1)) for i in pick]
    tok = _rng(seed, 6)
    return [{"prompt": tok.integers(0, vocab, int(lens[i]), np.int32),
             "max_new": m} for i, m in zip(pick, left)]
