"""From a profiler trace to the intervals the per-layer metrics read.

``read_profile`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
two plain lists, which are also what the tests record:

- ``device``: ``[chip, op name, start ns, duration ns]`` of every op on
  the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane.  The trace names
  an op by its whole HLO line (``%fusion.3 = f32[...] fusion(...)``); the
  name kept is the instruction's (``fusion.3``, ``jvp_tap_gemm_.2``);
- ``host``: ``[name, start ns, duration ns]`` of the benchmark's own
  ``bench:*`` annotations (the traced window, each dispatch, each wait).

``TraceView`` clips the device ops to the ``bench:window`` annotation and
answers what the metric readers ask: the window's length, the time in
which some op ran on each chip (the union of their intervals), the time of
ops by name, and the idle gaps, each labelled with the innermost host
annotation around it.
"""

from __future__ import annotations

import glob
import os

WINDOW = "bench:window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def op_name(hlo_line: str) -> str:
    """``%name = ...`` -> ``name``; any other name as it is."""
    return hlo_line.split(" = ", 1)[0].lstrip("%")


def read_profile(profile_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(paths[-1])
    device, host = [], []
    for plane in data.planes:
        chip = plane.name[len(DEVICE_PREFIX):]
        if plane.name.startswith(DEVICE_PREFIX) and chip.isdigit():
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.extend([int(chip), op_name(e.name), e.start_ns,
                                   e.duration_ns] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench:"))
    return {"device": device, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


class TraceView:
    def __init__(self, events: dict, chips: int):
        windows = [h for h in events["host"] if h[0] == WINDOW]
        if len(windows) != 1:
            raise ValueError(f"want one {WINDOW} annotation, found "
                             f"{len(windows)}")
        _, start, dur = windows[0]
        self.t0, self.t1 = float(start), float(start) + float(dur)
        self.chips = chips
        self.ops = []                      # (chip, name, a, b), clipped
        for chip, name, s, d in events["device"]:
            a, b = max(float(s), self.t0), min(float(s) + float(d), self.t1)
            if chip < chips and b > a:
                self.ops.append((chip, name, a, b))
        self.host = [(n, float(s), float(s) + float(d))
                     for n, s, d in events["host"]]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_intervals(self, chip: int = 0) -> list[list[float]]:
        return _union([(a, b) for c, _, a, b in self.ops if c == chip])

    @property
    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        total = sum(b - a for chip in range(self.chips)
                    for a, b in self.busy_intervals(chip))
        return total * 1e-9 / self.chips

    def op_seconds(self, parts: tuple[str, ...]) -> float:
        """Device seconds of the ops whose name contains one of ``parts``,
        averaged over the chips."""
        total = sum(b - a for _, name, a, b in self.ops
                    if any(p in name for p in parts))
        return total * 1e-9 / self.chips

    def top_ops(self, n: int = 10) -> list[list]:
        by_name: dict[str, float] = {}
        for _, name, a, b in self.ops:
            by_name[name] = by_name.get(name, 0.0) + (b - a) * 1e-9
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s / self.chips] for name, s in ranked]

    def _label(self, t: float) -> str:
        around = [(b - a, n) for n, a, b in self.host if a <= t <= b]
        return min(around)[1] if around else "outside"

    def idle_gaps(self, n: int = 10, chip: int = 0) -> list[list]:
        """The ``n`` longest gaps on ``chip`` in which no op ran, each
        labelled with the innermost ``bench:*`` span around its middle."""
        edges = [self.t0]
        for a, b in self.busy_intervals(chip):
            edges += [a, b]
        edges.append(self.t1)
        gaps = [(edges[i + 1] - edges[i], edges[i])
                for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        gaps.sort(reverse=True)
        return [[self._label(a + g / 2), g * 1e-9] for g, a in gaps[:n]]
