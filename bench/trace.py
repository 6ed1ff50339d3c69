"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

What is read, and from where:

* device planes ``/device:TPU:<n>``: the operations the chip ran, from
  the line named ``XLA Ops``; each event is named by its HLO instruction
  (``%fusion.12 = f32[...] fusion(...)``), a loop's ``while`` enclosing
  the operations of its body.  Busy time is the union of those intervals
  inside the traced window; idle is the rest.  The line ``Async XLA
  Ops`` holds the asynchronous starts and dones (copies, collectives).
* a Pallas kernel by its stable name (the ``name=`` the program gives
  ``pallas_call``, e.g. ``fused_sample``): :func:`kernel_ops` reads the
  compiled program's text for the ``tpu_custom_call`` instructions whose
  name scope ends in ``<name>/pallas_call``, and :meth:`Trace.kernel`
  sums the events of those instructions.
* collectives by their HLO operation (``collective-permute``,
  ``all-reduce``, ... and their ``-start`` / ``-done`` halves), on both
  lines.
* host spans: ``jax.profiler.TraceAnnotation`` events the harness writes
  on the host plane (``/host:CPU``).  The span named ``window`` bounds
  the traced window; every device idle gap is charged to the step span
  (``host_prep``, ``dispatch``, ``wait``) open at its midpoint, or to
  ``other`` (the harness's loop between steps).

Device and host events of one ``ProfileData`` share one clock (the
profiler converts device timestamps to the host's), which is what lets
an idle gap be put beside what the host was doing.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
ENCLOSING = ("while", "conditional", "call")
WINDOW_SPAN = "window"
STEP_SPANS = ("host_prep", "dispatch", "wait")   # a driver's step
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all", "send", "recv")

Interval = Tuple[int, int]          # [start_ns, end_ns)


@dataclass
class DeviceOps:
    """One device's operation events, (start_ns, end_ns, instruction),
    from ``XLA Ops`` and from ``Async XLA Ops``."""
    device: int
    events: List[Tuple[int, int, str]] = field(default_factory=list)
    async_events: List[Tuple[int, int, str]] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DeviceOps]
    host_spans: List[Tuple[int, int, str]]      # (start_ns, end_ns, name)
    kernels: Dict[str, str] = field(default_factory=dict)  # instr -> name
    names: Dict[str, str] = field(default_factory=dict)    # instr -> scope

    # ---------------------------------------------------------------- window
    def window(self) -> Interval:
        """The traced window: the harness's ``window`` span, else the
        extent of all device operations."""
        spans = [(s, e) for s, e, n in self.host_spans if n == WINDOW_SPAN]
        if spans:
            return min(s for s, _ in spans), max(e for _, e in spans)
        evs = [(s, e) for d in self.devices for s, e, _ in d.events]
        if not evs:
            raise ValueError("the trace holds no device operation")
        return min(s for s, _ in evs), max(e for _, e in evs)

    def window_s(self) -> float:
        w0, w1 = self.window()
        return (w1 - w0) / 1e9

    # ------------------------------------------------------------ busy/idle
    def busy_intervals(self, device: DeviceOps) -> List[Interval]:
        w0, w1 = self.window()
        return union((max(s, w0), min(e, w1)) for s, e, _ in device.events
                     if e > w0 and s < w1)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices
        that ran any."""
        busy = [total(self.busy_intervals(d)) for d in self.devices
                if d.events]
        if not busy:
            raise ValueError("the trace holds no device operation")
        return sum(busy) / len(busy) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    # --------------------------------------------------------- per-op time
    def _in_window(self, asynchronous: bool = False):
        w0, w1 = self.window()
        for d in self.devices:
            evs = d.events + (d.async_events if asynchronous else [])
            for s, e, instr in evs:
                if e > w0 and s < w1:
                    yield max(s, w0), min(e, w1), instr

    def op_seconds(self) -> Dict[str, float]:
        """Device seconds per operation (a kernel's stable name, else the
        end of the instruction's JAX name scope, else its HLO opcode),
        over all devices, inside the window; the enclosing loops and
        calls are left out, their bodies counted."""
        out: Dict[str, float] = collections.Counter()
        for s, e, instr in self._in_window():
            if opcode(instr) in ENCLOSING:
                continue
            kind = self.kernels.get(instr) or self.names.get(instr) \
                or opcode(instr)
            out[kind] += (e - s) / 1e9
        return dict(out)

    def kernel(self, name: str) -> Tuple[float, int]:
        """(device seconds, calls) of the Pallas kernel ``name`` inside
        the window, over all devices."""
        secs, calls = 0.0, 0
        for s, e, instr in self._in_window():
            if self.kernels.get(instr) == name:
                secs += (e - s) / 1e9
                calls += 1
        return secs, calls

    def collective_seconds(self) -> float:
        """Device seconds of collective operations inside the window,
        averaged over the devices that ran any operation."""
        n = sum(1 for d in self.devices if d.events) or 1
        secs = sum((e - s) / 1e9 for s, e, instr in self._in_window(True)
                   if is_collective(opcode(instr)))
        return secs / n

    # ------------------------------------------------------------ idle gaps
    def idle_gaps_by_host(self) -> Dict[str, float]:
        """Device idle seconds inside the window (averaged over devices),
        keyed by the step span open at each gap's midpoint."""
        w0, w1 = self.window()
        spans = sorted((s, e, n) for s, e, n in self.host_spans
                       if n in STEP_SPANS)
        starts = [s for s, _, _ in spans]
        out: Dict[str, float] = collections.Counter()
        devs = [d for d in self.devices if d.events]
        for d in devs:
            for g0, g1 in gaps(self.busy_intervals(d), w0, w1):
                mid = (g0 + g1) // 2
                i = bisect.bisect_right(starts, mid) - 1
                name = spans[i][2] if i >= 0 and spans[i][1] > mid \
                    else "other"
                out[name] += (g1 - g0) / 1e9 / len(devs)
        return dict(out)


# ---------------------------------------------------------------- helpers
def union(intervals) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], w0: int, w1: int) -> List[Interval]:
    out, t = [], w0
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if w1 > t:
        out.append((t, w1))
    return out


INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")


def instruction(text: str) -> str:
    """An HLO instruction's name from its text (``%fusion.12 = ...`` ->
    ``fusion.12``); a bare name stays as it is."""
    m = INSTR.match(text)
    return m.group(1) if m else text.strip().lstrip("%")


def opcode(instr: str) -> str:
    """``fusion.12`` -> ``fusion``; ``collective-permute-start.3`` ->
    ``collective-permute-start``."""
    return re.sub(r"\.\d+$", "", instr)


def is_collective(op: str) -> bool:
    return any(op == c or op.startswith(c + "-") for c in COLLECTIVES)


COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def kernel_ops(hlo_text: str) -> Dict[str, str]:
    """Compiled HLO text -> {instruction: kernel name} for every Pallas
    kernel compiled as a Mosaic custom call (name scope
    ``.../<kernel>/pallas_call``), and for every fusion that XLA built
    around one (the trace shows the fusion, so it is charged the
    kernel's time and whatever it fused with it)."""
    out, in_comp, comp = {}, {}, None
    lines = hlo_text.splitlines()
    for line in lines:
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        k = re.search(r'op_name="[^"]*?([A-Za-z0-9_]+)/pallas_call"', line)
        if k:
            out[instruction(line)] = k.group(1)
            if comp is not None:
                in_comp[comp] = k.group(1)
    for line in lines:
        c = re.search(r"calls=%?([\w.\-]+)", line)
        if c and c.group(1) in in_comp and " fusion(" in line:
            out[instruction(line)] = in_comp[c.group(1)]
    return out


def op_names(hlo_text: str) -> Dict[str, str]:
    """Compiled HLO text -> {instruction: the last two components of its
    JAX name scope} (what the breakdown calls an operation)."""
    out = {}
    for line in hlo_text.splitlines():
        m = OP_NAME.search(line)
        if m and INSTR.match(line):
            parts = [p for p in m.group(1).split("/")
                     if not p.startswith("jit(")]
            if parts:
                out[instruction(line)] = "/".join(parts[-2:])
    return out


# ----------------------------------------------------------------- loading
def from_profile(pd) -> Trace:
    """``jax.profiler.ProfileData`` -> :class:`Trace`."""
    devices, host = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = DeviceOps(int(m.group(1)))
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                out = dev.events if line.name == OPS_LINE \
                    else dev.async_events
                for ev in line.events:
                    s = int(ev.start_ns)
                    out.append((s, s + int(ev.duration_ns),
                                instruction(ev.name)))
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    host.append((s, s + int(ev.duration_ns), ev.name))
    if not any(d.events for d in devices):
        raise ValueError(f"no {OPS_LINE!r} events on any /device:TPU plane")
    return Trace(devices, host)


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]

