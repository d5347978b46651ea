"""Reduce a profiler trace (``.xplane.pb``) to the per-layer numbers.

The trace holds one plane per device (``/device:TPU:<i>``) with a line of
compiled programs (``XLA Modules``) and a line of the operations they ran
(``XLA Ops``), and host planes whose lines are threads.  The profiling
session runs longer than the slice it was started for, so the slice is
marked by a host annotation (``SLICE``, opened by ``harness.Tracer`` once
the session has started and closed before it stops), and each device's
clock is moved onto the host's by pairing program launches with program
starts.  The reduction keeps per device:

* busy time: the union of the intervals in which an operation ran, cut to
  the slice, so that it never exceeds the slice's length (``window_s``);
* time and count per program, matched by a part of its name
  (``jit_decode_step``), and per operation, matched likewise, over the
  whole trace;
* the idle gaps inside the slice, each named by the latest-started host
  event that spans its middle (what the host was doing meanwhile).

Every number is averaged over the devices of the chips a cell runs on.
Nothing here knows a cell: readers in ``layer_metrics/`` ask for what they
need.
"""
from __future__ import annotations

import re
from bisect import bisect_right
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from functools import lru_cache

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?$")
CONTAINERS = {"while", "conditional", "call"}
SLICE = "chipbench.slice"         # the host annotation around the slice
_SUFFIX = re.compile(r"(\.\d+)+$|\(\d+\)$")
_INST = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*\s*=")
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def base_name(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``jit_step(3)`` -> ``jit_step``."""
    return _SUFFIX.sub("", name)


@lru_cache(maxsize=None)
def op_key(text: str) -> tuple[str, str]:
    """(display name, opcode) of one ``XLA Ops`` event, whose name is the
    HLO instruction's text: ``%fusion.3 = f32[..] fusion(...)`` gives
    ("fusion", "fusion"); a Pallas kernel gives
    ("tpu_custom_call:<instruction>", "custom-call")."""
    m = _INST.match(text)
    if not m:
        return base_name(text), base_name(text)
    inst = m.group(1)
    rest = text[m.end():]
    target = _TARGET.search(rest)
    if target:
        return f"{target.group(1)}:{inst}", "custom-call"
    op = _OPCODE.search(rest)
    opcode = op.group(1) if op else inst
    return (inst if inst == opcode else f"{opcode}:{inst}"), opcode


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Sorted, disjoint intervals cut to ``[lo, hi]``."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _idle(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """The parts of ``[lo, hi]`` that sorted, disjoint ``busy`` intervals
    inside it leave uncovered."""
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


@dataclass
class Device:
    name: str
    busy: list = field(default_factory=list)  # merged op intervals in the slice
    modules: dict = field(default_factory=dict)       # base name -> [ns, n]
    ops: dict = field(default_factory=dict)     # (module, op name) -> [ns, n]
    launches: list = field(default_factory=list)  # (start, end, program)


@dataclass
class TraceSummary:
    devices: list
    gaps: list          # (name of host activity, ns) on the first device
    window_ns: int      # the slice's length

    @property
    def n(self) -> int:
        return len(self.devices)

    @property
    def busy_s(self) -> float:
        """Seconds of the slice in which an operation ran, averaged over
        the devices: at most ``window_s``."""
        return sum(_length(d.busy) for d in self.devices) / self.n / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def module(self, part: str) -> tuple[float, float]:
        """(seconds, executions) of programs whose name holds ``part``."""
        ns = cnt = 0
        for d in self.devices:
            for name, (t, c) in d.modules.items():
                if part in name:
                    ns += t
                    cnt += c
        return ns / self.n / 1e9, cnt / self.n

    def op(self, pattern: str, module: str = "") -> tuple[float, float]:
        """(seconds, calls) of operations whose name matches ``pattern``,
        inside programs whose name holds ``module``."""
        rx = re.compile(pattern, re.I)
        ns = cnt = 0
        for d in self.devices:
            for (mod, name), (t, c) in d.ops.items():
                if module in mod and rx.search(name):
                    ns += t
                    cnt += c
        return ns / self.n / 1e9, cnt / self.n

    def breakdown(self, top: int = 10) -> dict:
        ops: Counter = Counter()
        for d in self.devices:
            for (_, name), (t, _) in d.ops.items():
                if name.split(":")[0] not in CONTAINERS:
                    ops[name] += t
        gaps: Counter = Counter()
        for name, t in self.gaps:
            gaps[name] += t
        return {
            "device_ops": [[k, v / self.n / 1e9]
                           for k, v in ops.most_common(top)],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps.most_common(top)],
        }


def _device_planes(planes, chips) -> list:
    """The planes of the chips with these device ids, in that order."""
    by_name = {p.name: p for p in planes}
    names = [f"/device:TPU:{i}" for i in chips]
    missing = [n for n in names if n not in by_name]
    if missing or not names:
        found = sorted(n for n in by_name if n.startswith("/device:"))
        raise ValueError(f"the trace has no plane for {missing or 'any chip'}"
                         f"; its device planes: {found}")
    return [by_name[n] for n in names]


def _host_lines(planes) -> list:
    return [ln for p in planes if p.name.startswith("/host:")
            for ln in p.lines]


def _slice(planes) -> tuple[int, int]:
    """(start, end) of the ``SLICE`` annotation, on any host line, on the
    host's trace clock."""
    found = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
             for ln in _host_lines(planes) for e in ln.events
             if e.name == SLICE]
    if len(found) != 1:
        raise ValueError(f"the trace holds {len(found)} {SLICE!r} "
                         f"annotations, not one: it was not recorded by "
                         f"harness.Tracer, and no slice can be measured")
    return found[0]


def _host_events(planes) -> list[tuple[int, int, str]]:
    """Events of the Python threads (their functions and annotations)
    where the trace has them, else of every host thread; the slice's own
    annotation left out."""
    lines = _host_lines(planes)
    py = [ln for ln in lines if ln.name == "python"]
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for ln in (py or lines) for e in ln.events if e.name != SLICE]


def _name_gaps(host: list, gaps: list) -> list[tuple[str, int]]:
    """Name each (middle, length) gap by the latest-started host event
    that spans its middle: a sweep over host events sorted by start."""
    import heapq

    host = sorted(host)
    out, active, i = [], [], 0
    for mid, length in sorted(gaps):
        while i < len(host) and host[i][0] <= mid:
            s, e, name = host[i]
            heapq.heappush(active, (-s, e, name))
            i += 1
        while active and active[0][1] <= mid:
            heapq.heappop(active)
        out.append((base_name(active[0][2]).lstrip("$") if active
                    else "host: nothing traced", length))
    return out


def _launch_ends(planes) -> list[int]:
    return sorted(int(e.start_ns + e.duration_ns) for ln in _host_lines(planes)
                  for e in ln.events
                  if e.name == "PJRT_LoadedExecutable_Execute")


def _clock_offset(ends: list[int], dev: Device) -> int:
    """Host time minus device time: the k-th program launched on the host
    (``ends``: when each ``PJRT_LoadedExecutable_Execute`` returned) is the
    k-th to start on the device, a little after its launch returns; the
    median over the pairs."""
    n = min(len(ends), len(dev.launches))
    if not n:
        raise ValueError(f"no program launch on the host pairs with a "
                         f"program on {dev.name}: its clock cannot be "
                         f"moved onto the host's")
    d = sorted(ends[i] - dev.launches[i][0] for i in range(n))
    return d[n // 2]


def _reduce_device(p) -> Device:
    dev = Device(p.name)
    mods: list[tuple[int, int, str]] = []
    op_iv, coll_iv = [], []
    raw = []
    for line in p.lines:
        if line.name == "XLA Modules":
            for e in line.events:
                s0 = int(e.start_ns)
                mods.append((s0, s0 + int(e.duration_ns), base_name(e.name)))
        elif line.name in ("XLA Ops", "Async XLA Ops"):
            busy_line = line.name == "XLA Ops"
            for e in line.events:
                name, opcode = op_key(e.name)
                s0 = int(e.start_ns)
                iv = (s0, s0 + int(e.duration_ns))
                if COLLECTIVE.match(opcode):
                    coll_iv.append(iv)
                elif busy_line and opcode not in CONTAINERS:
                    op_iv.append(iv)
                if busy_line:
                    raw.append((iv[0], iv[1], name))
    mods.sort()
    starts = [m[0] for m in mods]
    per_mod: dict = defaultdict(lambda: [0, 0])
    for s0, e0, name in mods:
        m = per_mod[name]
        m[0] += e0 - s0
        m[1] += 1
    ops: dict = defaultdict(lambda: [0, 0])
    for s0, e0, name in raw:
        k = bisect_right(starts, s0) - 1
        mod = mods[k][2] if k >= 0 and mods[k][1] >= e0 else ""
        o = ops[(mod, name)]
        o[0] += e0 - s0
        o[1] += 1
    dev.busy = _union(op_iv + coll_iv)
    dev.launches = mods
    dev.modules, dev.ops = dict(per_mod), dict(ops)
    return dev


def reduce(profile, *, chips, min_gap_ns: int = 100_000) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` (or a path to an
    ``.xplane.pb``) recorded by ``harness.Tracer`` to a ``TraceSummary``
    of the chips with device ids ``chips``.  Raises where the trace has no
    slice, no plane of one of the chips, no launch to pair with a chip's
    programs, or no operation in the slice."""
    if not hasattr(profile, "planes"):
        from jax.profiler import ProfileData

        profile = ProfileData.from_file(str(profile))
    planes = list(profile.planes)
    start, end = _slice(planes)
    devices = [_reduce_device(p) for p in _device_planes(planes, chips)]
    ends = _launch_ends(planes)
    gaps = []
    for k, dev in enumerate(devices):
        off = _clock_offset(ends, dev)
        lo, hi = start - off, end - off
        dev.busy = _clip(dev.busy, lo, hi)
        if k == 0:
            gaps = _name_gaps(_host_events(planes),
                              [((s + e) // 2 + off, e - s)
                               for s, e in _idle(dev.busy, lo, hi)
                               if e - s >= min_gap_ns])
    summary = TraceSummary(devices=devices, gaps=gaps, window_ns=end - start)
    if not summary.busy_s > 0:
        raise ValueError("no operation ran on the chips inside the traced "
                         "slice")
    return summary
