"""From a profiler trace (``.xplane.pb``) to per-layer numbers.

The reduction, kept here so every run computes them the same way:

- a device is a plane named ``/device:TPU:<n>``; its operations are the
  events of its ``XLA Ops`` line, each named on a TPU by its whole HLO
  instruction (``%fusion.8 = f32[32] fusion(...), ...``), of which
  ``op_name`` keeps the instruction's name (``fusion.8``);
- busy time is the union of the operations' intervals inside the
  window, the idle share 1 - busy / window;
- the window is the harness's ``chipbench.window`` host span;
- a kernel's time is the summed duration of the operations whose name
  is a ``tpu_custom_call`` instruction of the compiled program that
  runs that kernel (``kernel_names`` reads the kernel's function name
  out of the instruction's serialized Mosaic body);
- a collective is an operation whose name starts with ``all-gather``,
  ``all-reduce``, ``reduce-scatter``, ``collective-permute`` or
  ``all-to-all``;
- an idle gap is attributed to the innermost host span (of any host
  thread) that covers its middle.
"""
from __future__ import annotations

import base64
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "chipbench.window"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
_KERNEL = re.compile(rb"([A-Za-z_][A-Za-z0-9_]*_kernel)(?![A-Za-z0-9_])")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=")


def find_xplane(tracedir: str) -> str:
    files = glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {tracedir}, found {files}")
    return files[0]


def remove(tracedir: str) -> None:
    shutil.rmtree(tracedir, ignore_errors=True)


def custom_calls(hlo_text: str) -> int:
    """How many Pallas kernel calls (``tpu_custom_call``) an optimized HLO
    module's text holds."""
    return hlo_text.count('custom_call_target="tpu_custom_call"')


def kernel_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: kernel function name} for every Pallas kernel
    call (``tpu_custom_call``) in an optimized HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _INSTR.match(line)
        body = re.search(r'"body":"([^"]+)"', line)
        if not m or not body:
            continue
        names = _KERNEL.findall(base64.b64decode(body.group(1)))
        if names:
            out[m.group(1)] = names[0].decode()
    return out


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device operation's event: a TPU
    trace names it ``%<name> = <shape> <opcode>(...)``; a bare name is
    kept as it is."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def union_ns(intervals: List[Tuple[int, int]]) -> Tuple[int, List[Tuple[int, int]]]:
    """(covered length, merged intervals) of [start, end) intervals."""
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


@dataclasses.dataclass
class Device:
    index: int
    busy_ns: int
    ops_ns: Dict[str, int]              # operation name -> summed duration
    kernels: Dict[str, List[int]]       # kernel -> [summed ns, launches]
    collective_ns: int
    gaps: List[Tuple[int, int]]         # idle [start, end) inside the window


@dataclasses.dataclass
class Summary:
    window_ns: int
    devices: List[Device]
    gap_names: List[Tuple[str, int]]    # (host span, gap ns), longest first

    def breakdown(self, top: int = 10) -> dict:
        """Device operations with the most time (mean over devices) and
        the longest idle gaps, named by the host span they fall in."""
        n = len(self.devices)
        total: Dict[str, int] = {}
        for d in self.devices:
            for k, v in d.ops_ns.items():
                total[k] = total.get(k, 0) + v
        ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
                "idle_gaps": [[k, v / 1e9] for k, v in self.gap_names[:top]]}


def _spans(plane) -> List[Tuple[int, int, str]]:
    out = []
    for line in plane.lines:
        for e in line.events:
            out.append((int(e.start_ns), int(e.start_ns + e.duration_ns), e.name))
    return out


def load(path: str):
    """The trace in an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def reduce(data, kernels: Dict[str, str], chips: Optional[int] = None) -> Summary:
    """Reduce one trace (a ``jax.profiler.ProfileData``); ``kernels`` maps
    instruction names to kernel names.  Only the first ``chips`` TPU
    planes count, the chips the cell uses (all when None)."""
    host, devs = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and (chips is None or int(m.group(1)) < chips):
            devs.append((int(m.group(1)), plane))
        elif plane.name.startswith("/host:"):
            host.extend(_spans(plane))
    windows = [(s, e) for s, e, name in host if name == WINDOW_SPAN]
    if len(windows) != 1 or not devs:
        raise RuntimeError(f"trace has {len(windows)} {WINDOW_SPAN!r} spans "
                           f"and {len(devs)} TPU planes")
    w0, w1 = windows[0]
    host = [h for h in host if h[2] != WINDOW_SPAN and h[1] > w0 and h[0] < w1]

    devices = []
    for index, plane in sorted(devs, key=lambda d: d[0]):
        ops = [e for line in plane.lines if line.name == OPS_LINE
               for e in line.events]
        if not ops:
            raise RuntimeError(f"{plane.name} has no {OPS_LINE!r} events; lines: "
                               f"{[line.name for line in plane.lines]}")
        intervals, ops_ns, kern, coll = [], {}, {}, 0
        for e in ops:
            s = max(int(e.start_ns), w0)
            t = min(int(e.start_ns + e.duration_ns), w1)
            if t <= s:
                continue
            intervals.append((s, t))
            name = op_name(e.name)
            ops_ns[name] = ops_ns.get(name, 0) + (t - s)
            k = kernels.get(name)
            if k is not None:
                acc = kern.setdefault(k, [0, 0])
                acc[0] += t - s
                acc[1] += 1
            if name.startswith(COLLECTIVES):
                coll += t - s
        busy, merged = union_ns(intervals)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        devices.append(Device(index, busy, ops_ns, kern, coll, gaps))

    named = []
    for d in devices[:1]:
        for s, e in d.gaps:
            mid = (s + e) // 2
            cover = [h for h in host if h[0] <= mid < h[1]]
            name = min(cover, key=lambda h: h[1] - h[0])[2] if cover else "(no host span)"
            named.append((name, e - s))
    named.sort(key=lambda g: -g[1])
    return Summary(window_ns=w1 - w0, devices=devices, gap_names=named)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader (``chipbench/metrics/<name>.py``)
    is given: the cell, the device's peaks, the reduced trace, and the
    work the traced window completed."""

    cfg: dict
    traffic: dict
    peak: Optional[dict]
    summary: Summary
    rounds: int                 # protocol rounds completed in the window
    syncs: int                  # syncs among them

    @property
    def window_s(self) -> float:
        return self.summary.window_ns / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, mean over the chips used."""
        d = self.summary.devices
        return sum(x.busy_ns for x in d) / len(d) / 1e9

    def kernel(self, name: str) -> Optional[Tuple[float, int]]:
        """(seconds summed over devices, launches) of one kernel, or None
        where the trace shows none."""
        s, n = 0, 0
        for d in self.summary.devices:
            if name in d.kernels:
                s += d.kernels[name][0]
                n += d.kernels[name][1]
        return (s / 1e9, n) if n else None

    @property
    def learners_per_chip(self) -> int:
        return self.cfg["learners"] // self.cfg["chips"]
