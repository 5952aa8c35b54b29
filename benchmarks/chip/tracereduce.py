"""From a profiler trace to the device's busy time, its top operations
and its idle gaps, each gap named by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the
operations of each TPU (the "XLA Ops" line of every ``/device:TPU:<n>``
plane) and the harness's own host spans (``bench.*`` annotations, on any
host thread).  ``reduce`` works on plain ``(name, start_ns, dur_ns)``
tuples, so it is tested on a small recorded trace without a chip.
"""

from __future__ import annotations

import collections
import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.window"


def load(logdir: str) -> tuple[dict[int, list], list]:
    """``({chip: [(op, start_ns, dur_ns)]}, [(span, start_ns, dur_ns)])``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {logdir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    device, host = {}, []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device[int(m.group(1))] = [
                        (e.name, e.start_ns, e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name.startswith("bench."))
    return device, host


# control flow whose event spans the operations of its body
_CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``fusion.172`` from the trace's ``%fusion.172 = bf16[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(device: dict[int, list], host: list, top: int = 10) -> dict:
    """Busy seconds (union of operation intervals, mean over chips), the
    window's length, the ``top`` operations by device seconds (mean over
    chips; loops and other control flow, whose events span their bodies,
    are left out of this list but not of the union) and the ``top`` longest idle gaps of chip 0 inside the
    window, each named by the ``bench.*`` host span that covers most of
    it ("host" where none does)."""
    windows = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    w0, w1 = windows[0]
    if not device:
        raise ValueError("the trace holds no device operations")
    chips = sorted(device)
    busy_ns, op_ns = [], collections.Counter()
    merged0 = None
    for c in chips:
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in device[c]
                   if s < w1 and s + d > w0]
        for (name, s, d) in device[c]:
            short = op_name(name)
            if s < w1 and s + d > w0 and not short.startswith(_CONTAINERS):
                op_ns[short] += min(s + d, w1) - max(s, w0)
        merged = _merge(clipped)
        busy_ns.append(sum(b - a for a, b in merged))
        if merged0 is None:
            merged0 = merged
    spans = [(n, s, s + d) for n, s, d in host if n != WINDOW_SPAN]
    gaps, t = [], w0
    for a, b in merged0 + [[w1, w1]]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)

    def name_of(a, b):
        best, cover = "host", 0
        for n, s, e in spans:
            ov = min(b, e) - max(a, s)
            if ov > cover:
                best, cover = n, ov
        return best

    gaps.sort(key=lambda g: g[0] - g[1])
    k = len(chips)
    return {
        "busy_s": sum(busy_ns) / k / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[n, v / k / 1e9] for n, v in op_ns.most_common(top)],
        "idle_gaps": [[name_of(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
    }
