"""What every cell shares: the chip check, the compile cache and clock, the
cell's files, the traced window, and the result line."""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
RUNS = ROOT / ".bench_runs"        # traces of --trace 1 runs (git-ignored)


class NoChip(SystemExit):
    pass


def require_chip(n_chips: int):
    """The devices of the cell; exits nonzero unless JAX sees a TPU with
    at least ``n_chips`` chips.  Never falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[bench] no TPU: jax found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        raise NoChip(3)
    if len(devices) < n_chips:
        print(f"[bench] the cell needs {n_chips} chips; jax found "
              f"{len(devices)}", file=sys.stderr)
        raise NoChip(3)
    return devices[:n_chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache/`` in the checkout (a fixed path, so
    the next run finds its entries).  Every program is cached, however fast
    it compiled."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileClock:
    """Sums JAX's compile-duration events (trace, lowering, XLA compile or
    persistent-cache fetch) and counts programs traced (each new
    specialization, whether its executable then comes from the persistent
    cache or not) and persistent-cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.compiles, self.hits


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry in BENCHMARK.json and its data files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"[bench] no workload {name!r}; "
                         f"known: {sorted(cells)}")
    entry = cells[name]
    read = lambda sub, n: json.loads((BENCH / sub / f"{n}.json").read_text())
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return {"name": name, "entry": entry, "cell": read("workloads", name),
            "config": read("configs", entry["config"]),
            "mix": read("traffic", entry["traffic"]),
            "end_to_end": end_to_end, "per_layer": per_layer}


def load_metric(name: str):
    """The reader of one per-layer metric, ``metrics/<name>.py``: its
    ``read(ctx)`` returns the value, or None where it finds nothing."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_info(devices) -> dict:
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class Profile:
    """A ``jax.profiler`` trace of part of the run, read back as events
    (``trace.events``) once it stops."""

    def __init__(self, name: str):
        self.dir = RUNS / name
        self.events = None

    def __enter__(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        return self

    def __exit__(self, *exc):
        import jax

        import trace_events

        jax.profiler.stop_trace()
        if exc[0] is None:
            self.events = trace_events.read_xplane(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        return False


def emit(result: dict) -> None:
    """The compared numbers beside their limits as the last lines of
    stderr, then the result as the last line of stdout."""
    for k, v in result["checks"].items():
        print(f"[bench] check {k}: {v['value']!r} limit {v['limit']!r} "
              f"{'ok' if v['value'] <= v['limit'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    out = {k: result[k] for k in ("correct", "attempted", "failed",
                                  "metrics", "device")}
    if "breakdown" in result:
        out["breakdown"] = result["breakdown"]
    out["checks"] = result["checks"]
    print(json.dumps(out), flush=True)
