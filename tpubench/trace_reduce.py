"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The trace has one plane per TPU (``/device:TPU:<n>``) whose ``XLA Ops``
line holds one event per operation the chip ran, and a host plane whose
thread lines hold the benchmark's ``jax.profiler.TraceAnnotation`` spans
(``bench.step``, ``bench.submit``, ``bench.wait_arrival``) on the same
clock. From them:

- the window: from the first to the last benchmark span;
- busy time: the union of the operation intervals inside the window
  (averaged over the chips); idle is the rest;
- Pallas time and XLA time: the summed durations of the operations that
  are Pallas kernels (Mosaic custom calls) and of all others;
- per ``bench.step`` span: the device time inside it, so a step's idle
  time is its length less that;
- idle gaps: each stretch of the window with no operation running,
  named by the benchmark span it falls in.

``python tpubench/trace_reduce.py <file.xplane.pb>`` prints the planes,
lines and a few events of a trace, to look at one by hand.
"""

from __future__ import annotations

import dataclasses
import re
import sys

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+")
OPS_LINE = "XLA Ops"
BENCH_PREFIX = "bench."
# An operation is a Pallas kernel when its HLO is a Mosaic custom call.
PALLAS_MARKERS = ("tpu_custom_call", "mosaic")


@dataclasses.dataclass
class Op:
    name: str
    start: float  # seconds, trace clock
    end: float
    pallas: bool


@dataclasses.dataclass
class TraceSummary:
    window: tuple[float, float]
    busy_s: float  # per chip, averaged
    pallas_s: float  # per chip, summed durations, averaged
    xla_s: float
    op_seconds: dict  # op name -> seconds (all chips)
    step_busy: list  # per bench.step span: (start, end, device-busy seconds)
    gaps: list  # (seconds, span name) of idle stretches, longest first
    n_chips: int

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def batches(self) -> list:
        """The ``bench.step`` spans in which the device ran: one per
        dispatched batch."""
        return [s for s in self.step_busy if s[2] > 0]

    def per_batch_ms(self, seconds: float) -> float | None:
        n = len(self.batches)
        return seconds / n * 1e3 if n else None

    def host_gap_ms(self) -> float | None:
        """Mean device idle time inside a step that dispatched a batch."""
        return self.per_batch_ms(sum((e - s) - busy for s, e, busy in self.batches))


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def _is_pallas(name: str, stats: dict) -> bool:
    text = " ".join([name] + [str(v) for v in stats.values()]).lower()
    return any(m in text for m in PALLAS_MARKERS)


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merged, sorted [start, end] rows of possibly overlapping ones."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [iv[0].tolist()]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _overlap(merged: np.ndarray, lo: float, hi: float) -> float:
    if len(merged) == 0:
        return 0.0
    return float(np.clip(np.minimum(merged[:, 1], hi) - np.maximum(merged[:, 0], lo), 0, None).sum())


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_ops(data) -> list[list[Op]]:
    chips = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                ops.append(Op(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9, _is_pallas(ev.name, _stats(ev))))
        chips.append(ops)
    return chips


def bench_spans(data) -> list[tuple[str, float, float]]:
    spans = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(BENCH_PREFIX):
                    spans.append((ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9))
    return sorted(spans, key=lambda s: s[1])


def reduce(data, max_gaps: int = 10) -> TraceSummary | None:
    """The trace's summary, or None when it holds no benchmark span or no
    device operation (nothing to read)."""
    chips = [c for c in device_ops(data) if c]
    spans = bench_spans(data)
    if not chips or not spans:
        return None
    lo, hi = spans[0][1], max(s[2] for s in spans)
    busy = pallas = xla = 0.0
    op_seconds: dict = {}
    merged_first = None
    for ops in chips:
        iv = np.asarray([[o.start, o.end] for o in ops], np.float64)
        merged = _union(iv)
        if merged_first is None:
            merged_first = merged
        busy += _overlap(merged, lo, hi)
        for o in ops:
            d = max(0.0, min(o.end, hi) - max(o.start, lo))
            if o.pallas:
                pallas += d
            else:
                xla += d
            op_seconds[o.name] = op_seconds.get(o.name, 0.0) + d
    n = len(chips)
    steps = [(s, e, _overlap(merged_first, s, e)) for name, s, e in spans if name == "bench.step"]
    gaps = []
    edges = np.clip(merged_first, lo, hi) if len(merged_first) else np.zeros((0, 2))
    cuts = np.concatenate([[lo], edges.reshape(-1), [hi]]).reshape(-1, 2)
    for g0, g1 in cuts:
        if g1 > g0:
            gaps.append((float(g1 - g0), _span_at(spans, (g0 + g1) / 2)))
    gaps.sort(key=lambda g: -g[0])
    return TraceSummary(
        window=(lo, hi), busy_s=busy / n, pallas_s=pallas / n, xla_s=xla / n,
        op_seconds=op_seconds, step_busy=steps, gaps=gaps[:max_gaps], n_chips=n,
    )


def _span_at(spans, t: float) -> str:
    """The innermost benchmark span that holds ``t`` ("between spans" if none)."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
        if s > t:
            break
    return best[0] if best else "between spans"


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    ops = sorted(summary.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[name, sec] for name, sec in ops],
        "idle_gaps": [[name, sec] for sec, name in summary.gaps[:top]],
    }


def describe(path: str, n_events: int = 5) -> None:
    data = load(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:n_events]:
                print(f"    {ev.name!r} start_ns={ev.start_ns} dur_ns={ev.duration_ns} stats={_stats(ev)}")


if __name__ == "__main__":
    describe(sys.argv[1])
