"""Real-clock probes (DESIGN.md Sec. 11): compile counters, and the
names under which the engine shows up in a JAX profiler trace.

- **Silent recompiles.** The repo's compile-cache contracts (frozen
  hashable substrates keying ``engine._jitted``, one executable per
  (substrate, kind) sweep group — DESIGN.md Secs. 7-8) are easy to
  break invisibly: a recompile costs seconds and shows up in no test.
  :class:`CompileCounter` counts backend compiles via
  ``jax.monitoring``'s ``/jax/core/compile/backend_compile_duration``
  event — fired exactly once per XLA compilation, cache hits fire
  nothing — making "this call must not compile anything new" an
  assertable property (tests/test_telemetry.py pins the engine's
  cache-keying contract with it).

- **Where a round and an experiment spend their time.** The protocol
  step (``engine._make_step``) runs its three phases under
  ``jax.named_scope`` (``SCOPE_*``): the names reach the compiled
  program only as HLO ``op_name`` metadata, so the instructions and
  their floats are those of an unscoped step, and a device trace's
  operations map to a phase through the optimized HLO.  ``engine.run``
  opens ``jax.profiler.TraceAnnotation`` host spans (``ENGINE_*``) on
  the clock the profiler aligns the device planes to; with no profiler
  running an annotation records nothing.

The jax.monitoring API registers listeners for the life of the
process; this module installs ONE module-level listener lazily and
dispatches to whatever counters are currently active, so counters nest
and never leak.
"""
from __future__ import annotations

from typing import List

import jax

#: ``jax.named_scope`` names of the protocol step's phases: predict and
#: update with the error terms, the dynamic check (with, on a mesh, its
#: violation psum) and the sync ``lax.cond`` (with its all_gather)
SCOPE_PREDICT_UPDATE = "predict_update"
SCOPE_CHECK = "check"
SCOPE_SYNC = "sync"
STEP_SCOPES = (SCOPE_PREDICT_UPDATE, SCOPE_CHECK, SCOPE_SYNC)

#: host spans of ``engine.run``: the whole call, then its four phases in
#: order — uploading the streams and parameters, dispatching the jitted
#: program, copying the six outputs back (which waits on the device),
#: and assembling the ``SimResult``
ENGINE_RUN = "repro.engine.run"
ENGINE_UPLOAD = "repro.engine.upload"
ENGINE_DISPATCH = "repro.engine.dispatch"
ENGINE_COPY_BACK = "repro.engine.copy_back"
ENGINE_ASSEMBLE = "repro.engine.assemble"
ENGINE_PHASES = (ENGINE_UPLOAD, ENGINE_DISPATCH, ENGINE_COPY_BACK,
                 ENGINE_ASSEMBLE)

#: The monitoring event jax fires once per actual XLA backend compile.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_active_counters: List["CompileCounter"] = []
_listener_installed = False


def _on_event_duration(event: str, duration_secs: float, **_kw) -> None:
    if event != _COMPILE_EVENT:
        return
    for c in _active_counters:
        c.compiles += 1
        c.compile_secs += duration_secs


def _install_listener() -> None:
    global _listener_installed
    if not _listener_installed:
        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration)
        _listener_installed = True


class CompileCounter:
    """Context manager counting XLA backend compiles in its scope.

    ::

        with CompileCounter() as c:
            engine.run(cfg, pcfg, X, Y)      # may compile
            n = c.compiles
            engine.run(cfg, pcfg, X, Y)      # cache hit
        assert c.compiles == n               # no recompile

    ``compiles`` counts every executable XLA built — the jitted scan
    plus any small eager ops not yet in the process-wide cache — so
    regression tests assert *deltas* ("the second call adds zero"),
    which is exactly the cache-contract shape.  Counters may nest;
    each sees all compiles while it is active.
    """

    def __init__(self) -> None:
        self.compiles = 0
        self.compile_secs = 0.0

    def __enter__(self) -> "CompileCounter":
        _install_listener()
        _active_counters.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _active_counters.remove(self)
