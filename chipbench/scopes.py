"""The program's own names in a profiler trace: the protocol step's
named scopes and ``engine.run``'s host spans.

- a scope is one of ``SCOPES``, the ``jax.named_scope`` names the engine
  gives the phases of its step.  They reach the compiled program only as
  HLO ``op_name`` metadata, and a TPU trace's operations carry no scope,
  so ``scope_names`` maps instructions to scopes from the optimized HLO
  text, as ``trace.kernel_names`` maps kernels.  An instruction belongs
  to the first scope that is a whole component of its ``op_name`` path;
- a scope's time on a chip is the time of its outermost instructions
  (``scope_roots``: scoped, and called from no scoped instruction).  The
  trace's ``XLA Ops`` line nests (``while`` holds ``cond`` holds
  ``fusion``), so the instructions a scoped ``cond`` runs lie inside
  its interval: summing only the outermost ones is the union of the
  scope's operations, with nothing counted twice;
- a host span is a ``jax.profiler.TraceAnnotation`` of ``engine.run``
  (``SPAN_PREFIX``), and the idle time inside it is the part of the
  chips' idle gaps (``trace.Device.gaps``) that it covers.

A program without the scopes or spans gives no scoped instruction and
no span: the readers of these numbers then return None.
"""
from __future__ import annotations

import functools
import json
import re
from typing import Dict, List, Optional, Tuple

SCOPES = ("predict_update", "check", "sync")
SPAN_PREFIX = "repro.engine."
RUN_SPAN = SPAN_PREFIX + "run"
PHASE_SPANS = tuple(SPAN_PREFIX + p
                    for p in ("upload", "dispatch", "copy_back", "assemble"))

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS_ONE = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([^\s,{}]+)")
_CALLS_MANY = re.compile(
    r"\b(?:calls|branch_computations|called_computations)=\{([^}]*)\}")


def _parse(hlo_text: str):
    """(instruction -> (computation, scope or None), computation -> the
    instructions that call it) of an HLO module's text."""
    instrs: Dict[str, Tuple[str, Optional[str]]] = {}
    callers: Dict[str, List[str]] = {}
    comp = ""
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            if m and line.rstrip().endswith("{"):
                comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        scope = None
        if op:
            scope = next((c for c in op.group(1).split("/") if c in SCOPES), None)
        instrs[name] = (comp, scope)
        called = _CALLS_ONE.findall(line)
        for group in _CALLS_MANY.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",") if c.strip()]
        for c in called:
            callers.setdefault(c, []).append(name)
    return instrs, callers


def scope_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: scope} for every instruction of an optimized HLO
    module's text whose ``op_name`` path holds one of ``SCOPES``."""
    instrs, _ = _parse(hlo_text)
    return {name: scope for name, (_, scope) in instrs.items() if scope}


def scope_roots(hlo_text: str) -> Dict[str, str]:
    """The outermost scoped instructions, {instruction name: scope}: those
    that no scoped instruction calls, directly or through others."""
    instrs, callers = _parse(hlo_text)

    @functools.lru_cache(maxsize=None)
    def under_scope(comp: str) -> bool:
        return any(instrs[c][1] is not None or under_scope(instrs[c][0])
                   for c in callers.get(comp, ()) if c in instrs)

    return {name: scope for name, (comp, scope) in instrs.items()
            if scope and not under_scope(comp)}


def scope_ns(ops_ns: Dict[str, int], roots: Dict[str, str]) -> Dict[str, int]:
    """{scope: device ns} of one chip, from its summed time per operation
    (``trace.Device.ops_ns``) and the outermost scoped instructions."""
    out = {s: 0 for s in SCOPES}
    for name, ns in ops_ns.items():
        scope = roots.get(name)
        if scope is not None:
            out[scope] += ns
    return out


@functools.lru_cache(maxsize=None)
def _program_roots(key: str) -> Dict[str, str]:
    from chipbench import harness, streams

    cfg, traffic = json.loads(key)
    system = harness.system_module(cfg).build(cfg, traffic["protocol"])
    X, Y = streams.FAMILIES[cfg["stream"]](cfg["rounds"], cfg["learners"], cfg["dim"])
    return scope_roots(system.hlo_text(X, Y))


def program_roots(cfg: dict, traffic: dict) -> Dict[str, str]:
    """``scope_roots`` of the program a cell runs, compiled again from its
    configuration (the shapes decide the program, not the seed); once
    per process and cell."""
    return _program_roots(json.dumps([cfg, traffic], sort_keys=True))


def busiest_scope_ns(r, scope: str) -> Optional[int]:
    """Device ns of ``scope`` on the busiest chip of a ``trace.Reading``,
    or None where the program names no such scope or the trace shows
    none of its operations."""
    roots = program_roots(r.cfg, r.traffic)
    busiest = max(r.summary.devices, key=lambda d: d.busy_ns)
    ns = scope_ns(busiest.ops_ns, roots)[scope]
    return ns or None


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------


def host_spans(data, prefix: str = SPAN_PREFIX) -> List[Tuple[int, int, str]]:
    """(start ns, end ns, name) of every host event of a trace (a
    ``jax.profiler.ProfileData``) whose name starts with ``prefix``, in
    order of start, a span before the spans it holds."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((int(e.start_ns), int(e.start_ns + e.duration_ns),
                                e.name))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def idle_in_spans(summary, spans: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """{span name: ns} of the chips' idle time inside the window that the
    spans of each name cover, mean over the chips of a ``trace.Summary``."""
    out: Dict[str, int] = {}
    for s0, s1, name in spans:
        total = 0
        for d in summary.devices:
            total += sum(max(0, min(e, s1) - max(s, s0)) for s, e in d.gaps)
        out[name] = out.get(name, 0) + total // len(summary.devices)
    return out
