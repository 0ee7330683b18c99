"""Observability subsystem (DESIGN.md Sec. 11).

- trace:   structured span/counter/instant recorder exporting
           Chrome-trace-event JSON (Perfetto-viewable) on the
           *simulated* event clock — byte-identical under seed.
- monitor: the paper's loss-proportionality criterion as a live
           per-round check (CriterionMonitor), integer-exact against
           the Sec. 3 DeviceLedger for every driver and substrate.
- probe:   backend-compile counters on jit cache misses
           (CompileCounter), and the names of the engine's phases in a
           JAX profiler trace: ``jax.named_scope`` in the protocol
           step, ``jax.profiler.TraceAnnotation`` spans in
           ``engine.run``.

Everything here is host-side and opt-in: no tracer, no cost.  The
jitted scan core carries only the step's scope names, as HLO op_name
metadata: no traced value enters the carry, and the compiled
instructions are those of an unscoped step.
"""
from . import monitor, probe, trace
from .monitor import (CriterionMonitor, MonitorSeries, monitor_population,
                      monitor_result, monitor_sweep, unit_bytes_of)
from .probe import CompileCounter
from .trace import (PID_MONITOR, PID_NETWORK, PID_RUNTIME, PID_SERVING,
                    TICKS_PER_UNIT, Tracer)

__all__ = [
    "monitor", "probe", "trace",
    "CriterionMonitor", "MonitorSeries", "monitor_population",
    "monitor_result", "monitor_sweep", "unit_bytes_of",
    "CompileCounter",
    "PID_MONITOR", "PID_NETWORK", "PID_RUNTIME", "PID_SERVING",
    "TICKS_PER_UNIT", "Tracer",
]
