"""Compile spans: what jax traces, lowers and compiles while ``TRACER``
is enabled.

jax reports each stage of a compile to its ``jax.monitoring`` duration
listeners once the stage has ended.  :func:`install` registers one
listener that files each report as a ``jax.compile`` span through
:meth:`~.trace.Tracer.record`, with ``stage`` ``trace`` (jaxpr
tracing), ``lower`` (jaxpr → MLIR) or ``compile`` (the backend compile,
or its persistent-cache load) and ``fun``, the function's name.  A
``stage="trace"`` span inside a measured window is a retrace.  While
``TRACER`` is disabled the listener returns at once.
"""
from __future__ import annotations

import threading

from .trace import TRACER

__all__ = ["STAGES", "install"]

#: jax's compile-stage events (``jax/_src/dispatch.py``) → ``stage``
STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}

_lock = threading.Lock()
_installed = False


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if not TRACER.enabled:
        return
    stage = STAGES.get(event)
    if stage is not None:
        TRACER.record("jax.compile", duration_secs, stage=stage,
                      fun=kwargs.get("fun_name", "?"))


def install() -> None:
    """Register the listener with ``jax.monitoring``, once per
    process."""
    global _installed
    with _lock:
        if _installed:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
