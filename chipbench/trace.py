"""Reduce a profiler trace (``.xplane.pb``) to the per-layer numbers.

The trace holds one plane per device (``/device:TPU:<i>``) with a line of
compiled programs (``XLA Modules``) and a line of the operations they ran
(``XLA Ops``), and host planes whose lines are threads.  The reduction keeps
per device:

* busy time: the union of the intervals in which an operation ran;
* time and count per program, matched by a part of its name
  (``jit_decode_step``), and per operation, matched likewise;
* the idle gaps between busy intervals, each named by the latest-started
  event of the host's Python thread that spans its middle (what the host
  was doing meanwhile), the device clock moved onto the host's by pairing
  program launches with program starts.

Every number is averaged over the devices in the trace.  Nothing here
knows a cell: readers in ``layer_metrics/`` ask for what they need.
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


@dataclass
class Device:
    name: str
    busy: list = field(default_factory=list)          # merged op intervals
    modules: dict = field(default_factory=dict)       # base name -> [ns, n]
    ops: dict = field(default_factory=dict)     # (module, op name) -> [ns, n]
    launches: list = field(default_factory=list)  # (start, end, program)


@dataclass
class TraceSummary:
    devices: list
    gaps: list          # (name of host activity, ns) on the first device
    span_ns: int        # first to last device event

    @property
    def n(self) -> int:
        return max(1, len(self.devices))

    @property
    def busy_s(self) -> float:
        return sum(_length(d.busy) for d in self.devices) / self.n / 1e9

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


def _device_planes(planes):
    return sorted((p for p in planes if p.name.startswith("/device:TPU")),
                  key=lambda p: p.name)


def _host_events(planes) -> list[tuple[int, int, str]]:
    """Events of the Python thread (its functions and annotations) where
    the trace has one, else of every host thread."""
    lines = [ln for p in planes if p.name.startswith("/host:")
             for ln in p.lines]
    py = [ln for ln in lines if ln.name == "python"]
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for ln in (py or lines) for e in ln.events]


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


def _clock_offset(planes, modules: list) -> int:
    """Host time minus device time: the k-th program launched on the host
    (``PJRT_LoadedExecutable_Execute``) is the k-th to start on the
    device, a little after its launch returns; the median over the pairs."""
    ends = sorted(int(e.start_ns + e.duration_ns) for p in planes
                  if p.name.startswith("/host:") for ln in p.lines
                  for e in ln.events
                  if e.name == "PJRT_LoadedExecutable_Execute")
    n = min(len(ends), len(modules))
    if not n:
        return 0
    d = sorted(ends[i] - modules[i][0] for i in range(n))
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


def reduce(profile, *, min_gap_ns: int = 100_000) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` (or a path to an
    ``.xplane.pb``) to a ``TraceSummary``."""
    if not hasattr(profile, "planes"):
        from jax.profiler import ProfileData

        profile = ProfileData.from_file(str(profile))
    planes = list(profile.planes)
    devices = [_reduce_device(p) for p in _device_planes(planes)]
    busy = [d.busy for d in devices if d.busy]
    span = (max(b[-1][1] for b in busy) - min(b[0][0] for b in busy)
            if busy else 0)
    gaps = []
    if devices and devices[0].busy:
        b = devices[0].busy
        off = _clock_offset(planes, devices[0].launches)
        gaps = _name_gaps(_host_events(planes),
                          [((e0 + s1) // 2 + off, s1 - e0)
                           for (_, e0), (s1, _) in zip(b, b[1:])
                           if s1 - e0 >= min_gap_ns])
    return TraceSummary(devices=devices, gaps=gaps, span_ns=span)
