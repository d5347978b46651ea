"""Per-layer metrics of a traced run: reduce the trace, then ask each
metric's reader in ``layer_metrics/`` for its number."""
from __future__ import annotations

from chipbench import harness, spec, trace


def read_all(cell, tracer: harness.Tracer, counters: dict,
             device: dict) -> tuple[dict, dict, dict]:
    """(metrics, device with busy_s and window_s, breakdown).  Busy time
    and the window are two readings of the traced slice, on the chips the
    cell runs on."""
    chips = [d.id for d in harness.chips(cell.chips)]
    summary = trace.reduce(tracer.xplane(), chips=chips)
    tracer.cleanup()
    ctx = {
        "cell": cell.name,
        "config": cell.config,
        "mix": cell.mix,
        "chips": cell.chips,
        "peak": harness.peaks(device["kind"]),
        "trace": summary,
        "window_s": summary.window_s,
        "counters": counters,
    }
    metrics = {}
    for m in cell.per_layer:
        value = spec.load_metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = float(value)
    device = dict(device, busy_s=summary.busy_s, window_s=summary.window_s)
    return metrics, device, summary.breakdown()
