"""SweepScope — runtime observability for the selected-inversion stack.

The static layers (PlanLint, the α-per-round simulator, HloLint) reason
about what the schedule *should* do; this package measures what it
*does*:

* ``trace``    — nested span tracer with a thread-safe ring buffer and a
  near-zero-cost disabled path; the engine and serve layers emit spans
  through the module-level ``TRACER``.
* ``compiles`` — a ``jax.monitoring`` listener that files every jax
  trace, lowering and compile as a ``jax.compile`` span while the
  tracer is enabled (installed by ``core.engine``).
* ``registry`` — unified metrics registry (counters / gauges /
  histograms with labels), one ``snapshot()`` and a prometheus-style
  text dump; ``engine.stats()`` and ``serve.metrics`` register into it.
* ``rounds``   — ``engine.profile_rounds()``: re-executes the overlapped
  sweep as per-round jitted segments with ``block_until_ready`` fencing
  and joins the measured timeline against the plan's wire tables
  (residuals, inbound-skew report, α/β fit).
* ``export``   — Chrome-trace / Perfetto JSON export of spans, round
  timelines and serve request lifecycles.

``rounds`` and ``export`` import the core/serve layers, so they are NOT
imported here — ``import repro.obs`` must stay cheap and cycle-free for
``core.engine`` (which imports ``obs.trace`` at module level).
"""
from . import registry, trace                                  # noqa: F401
from .registry import REGISTRY, MetricsRegistry                # noqa: F401
from .trace import TRACER, Span, Tracer                        # noqa: F401

__all__ = [
    "trace", "registry",
    "TRACER", "Tracer", "Span",
    "REGISTRY", "MetricsRegistry",
]
