"""Dispatch recording for ``layers.qeinsum`` and the fused-kernel wrappers.

``qeinsum`` and the ``kernels.ops`` wrappers run Python only while jax is
TRACING a computation; once jit has compiled a specialization they never
run again.  That makes them the perfect zero-overhead place to count
dispatches: a recorder installed here observes **one event per compiled
specialization** (per backend, shape, and dtype), at strictly zero
steady-state cost — the hot decode loop replays compiled XLA and never
touches these counters again.  Interpret the counts accordingly: they
answer "which backends did this engine compile, and what does one step
move analytically", not "how many GEMMs ran per second".

The recorder is a module global rather than a field threaded through
model code because ``qeinsum`` is called deep inside jitted model
forwards that know nothing about engines.  ``recording(obs)`` installs
it for the dynamic extent of a block (the engine wraps ``step()``), and
``active()`` is the single cheap check instrumented call-sites make.

This module imports nothing from the rest of ``repro`` (call-sites pass
plain ints), so instrumenting ``models``/``kernels`` introduces no
import cycles.

``programs_traced()`` is the process-wide count of programs jax has traced
(its ``jaxpr_trace_duration`` monitoring event, one listener registered on
first use): a count that moves across a call means jit compiled a new
specialization there, GEMMs or none.
"""
from __future__ import annotations

from contextlib import contextmanager

_active = None
_traced = 0
_listening = False
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def active():
    """The installed DispatchRecorder, or None (the common fast path)."""
    return _active


@contextmanager
def recording(recorder):
    """Install ``recorder`` as the active dispatch recorder for the block.
    Pass None to keep recording disabled (still a valid context)."""
    global _active
    prev = _active
    _active = recorder
    try:
        yield recorder
    finally:
        _active = prev


def _on_duration(event, duration, **_):
    global _traced
    if event == _TRACE_EVENT:
        _traced += 1


def programs_traced() -> int:
    """Programs jax has traced in this process since the first call."""
    global _listening
    if not _listening:
        import jax  # lazy: after launch._tpenv's device forcing
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return _traced


class DispatchRecorder:
    """Counts qeinsum/kernel dispatches into a MetricsRegistry.

    Bytes are analytic: for a packed-NVFP4 GEMM the weight-side traffic
    is ``codes + scales + tensor_scale`` (the packed representation that
    actually crosses HBM), for dense it is the weight array's nbytes.
    """

    def __init__(self, registry):
        self._gemm = registry.counter(
            "qeinsum_dispatch_total",
            "qeinsum GEMM dispatches per backend "
            "(counted once per compiled specialization)",
            labels=("backend",))
        self._gemm_bytes = registry.counter(
            "qeinsum_weight_bytes_total",
            "analytic weight bytes moved per qeinsum dispatch, by backend",
            labels=("backend",))
        self._kernel = registry.counter(
            "kernel_dispatch_total",
            "fused/primitive Pallas kernel wrapper dispatches "
            "(counted once per compiled specialization)",
            labels=("kernel",))

    def gemm(self, backend: str, weight_bytes: int = 0) -> None:
        self._gemm.labels(backend=backend).inc()
        if weight_bytes:
            self._gemm_bytes.labels(backend=backend).inc(float(weight_bytes))

    def kernel(self, name: str) -> None:
        self._kernel.labels(kernel=name).inc()
