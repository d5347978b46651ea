"""What every runner shares: devices, the compile cache, set-up and compile
clocks, tracing, the result line and the checks printed beside it."""
from __future__ import annotations

import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

from chipbench.spec import ROOT

PEAKS_FILE = ROOT / "chipbench" / "peaks.json"
TRACE_DIR = ROOT / "chipbench" / ".traces"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where the environment names one, else ``<checkout>/.jax_cache`` (a
    fixed path, so every run of this checkout finds what the first
    compiled).  Every program is cached, however quick its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips(n: int, require_chip: bool = True) -> list:
    """The first ``n`` devices; raises ``NoChip`` off the accelerator."""
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform not in ("tpu", "gpu"):
        raise NoChip(f"JAX found no accelerator (platform "
                     f"{devs[0].platform})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; unknown kinds fail."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def memory_stats(devs) -> list[dict]:
    return [dict(d.memory_stats() or {}) for d in devs]


def device_info(devs) -> dict:
    """The device as JAX reports it.  ``memory_peak_bytes`` is, on the
    fullest chip, the larger of the peak of buffers in use and what is held
    now counting the space compiled programs reserved for their
    temporaries, which ``peak_bytes_in_use`` leaves out (both were held at
    once; the sum of the two peaks may not have been)."""
    peak = 0
    for st in memory_stats(devs):
        held = int(st.get("bytes_in_use", 0)) + int(st.get("bytes_reserved",
                                                           0))
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)), held)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileClock:
    """Counts the programs JAX compiles (persistent-cache misses) while
    alive."""

    def __init__(self):
        import jax

        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1


@dataclass
class Check:
    """One number compared with its limit: ``value <= limit`` passes; a
    number with no limit set never does."""

    name: str
    value: float
    limit: float | None

    @property
    def ok(self) -> bool:
        return bool(self.limit is not None and math.isfinite(self.value)
                    and self.value <= self.limit)


@dataclass
class Result:
    attempted: int = 0
    failed: int = 0
    metrics: dict = field(default_factory=dict)     # name -> value
    device: dict = field(default_factory=dict)
    checks: list = field(default_factory=list)      # [Check]
    breakdown: dict | None = None
    notes: dict = field(default_factory=dict)       # printed to stderr only
    controls: dict = field(default_factory=dict)    # name -> Result of a
    #                                                 control in the program's place

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks) \
            and self.failed == 0


def result_line(res: Result, units: dict) -> str:
    out = {
        "correct": res.correct,
        "attempted": int(res.attempted),
        "failed": int(res.failed),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in res.metrics.items()},
        "device": res.device,
    }
    if res.breakdown is not None:
        out["breakdown"] = res.breakdown
    out["checks"] = {c.name: {"value": float(c.value), "limit": c.limit}
                     for c in res.checks}
    return json.dumps(out)


def print_checks(res: Result) -> None:
    for c in res.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(f"correct: {res.correct} (failed {res.failed} of "
          f"{res.attempted})", file=sys.stderr, flush=True)


class Tracer:
    """The profiler around one slice of a window, into a directory of the
    checkout that is removed once the trace has been reduced.

    ``start_in(delay, length)`` traces ``length`` seconds starting
    ``delay`` seconds from now, from a timer thread, while the caller's
    thread runs on; ``join()`` waits for it.  ``t0``/``t1`` are the slice's
    ends on the perf_counter clock."""

    def __init__(self, enabled: bool, tag: str):
        self.enabled = enabled
        self.dir = TRACE_DIR / tag
        self.t0 = self.t1 = 0.0
        self._threads: list = []

    def start_in(self, delay: float, length: float) -> None:
        import threading

        import jax

        if not self.enabled:
            return

        def run():
            time.sleep(delay)
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True)
            jax.profiler.start_trace(str(self.dir))
            self.t0 = time.perf_counter()
            time.sleep(length)
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()

        th = threading.Thread(target=run, daemon=True)
        th.start()
        self._threads.append(th)

    def join(self) -> None:
        for th in self._threads:
            th.join()

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def xplane(self) -> Path:
        found = sorted(self.dir.glob("**/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
        return found[-1]

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
