"""From a profiler trace to the numbers the per-layer readers take.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps what the reduction needs, as plain lists that ``save_events`` /
``load_events`` round-trip through JSON:

* per device plane (``/device:TPU:<n>``): its ``XLA Ops`` events and its
  ``XLA Modules`` events (one per program run: ``jit_train_step(7)``);
* on the host: the benchmark's own ``perfbench.*`` annotations, which say
  what the host was doing.

Each event is ``[start_ns, duration_ns, name]``. ``Summary`` then gives
the busy time (the union of op intervals), the traced window, device
time by program, the heaviest ops and the longest idle gaps.
"""
from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "perfbench."
WINDOW_SPAN = "perfbench.window"


def load_xplane(path) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = out["devices"].setdefault(plane.name,
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key].extend([e.start_ns, e.duration_ns, op_name(e.name)]
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.start_ns, e.duration_ns, e.name]
                    for e in line.events if e.name.startswith(HOST_PREFIX))
    return out


def op_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``): keep ``fusion.12``."""
    return name.split(" = ")[0].lstrip("%")


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def save_events(events: dict, path):
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end]`` intervals, clipped to ``[lo, hi]``."""
    merged: list = []
    for s, d in sorted((s, d) for s, d, *_ in intervals):
        s, e = max(s, lo), min(s + d, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def program_name(module_event_name: str) -> str:
    """``jit_train_step(12)`` → ``jit_train_step``."""
    return module_event_name.split("(")[0]


class Summary:
    """The reduction of one traced window."""

    def __init__(self, events: dict):
        self.events = events
        devices = events["devices"]
        if not devices:
            raise ValueError("the trace has no device plane")
        spans = [e for e in events["host"] if e[2] == WINDOW_SPAN]
        if spans:
            s, d, _ = max(spans, key=lambda e: e[1])
            self.lo, self.hi = s, s + d
        else:
            every = [e for dev in devices.values() for e in dev["ops"]]
            self.lo = min(s for s, _, _ in every)
            self.hi = max(s + d for s, d, _ in every)
        self.window_s = (self.hi - self.lo) / 1e9
        self._busy = {name: union(dev["ops"] or dev["modules"], self.lo,
                                  self.hi)
                      for name, dev in devices.items()}

    @property
    def n_devices(self) -> int:
        return len(self._busy)

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        return sum(sum(e - s for s, e in iv) for iv in self._busy.values()) \
            / 1e9 / self.n_devices

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program(self, name: str, window_only: bool = True) -> tuple:
        """(runs, device seconds) of the program ``name``, summed over
        devices: its ``XLA Modules`` events that lie in the window, or,
        with ``window_only=False``, anywhere in the trace."""
        runs, ns = 0, 0.0
        for dev in self.events["devices"].values():
            for s, d, n in dev["modules"]:
                if program_name(n) == name and (not window_only or (
                        s >= self.lo and s + d <= self.hi)):
                    runs += 1
                    ns += d
        return runs, ns / 1e9

    def programs(self) -> dict:
        """Device seconds by program over the window, summed over
        devices."""
        out: dict = defaultdict(float)
        for dev in self.events["devices"].values():
            for s, d, n in dev["modules"]:
                if s >= self.lo and s + d <= self.hi:
                    out[program_name(n)] += d / 1e9
        return dict(out)

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` op names that took most device time in the window,
        summed over devices: ``[[name, seconds], ...]``."""
        out: dict = defaultdict(float)
        for dev in self.events["devices"].values():
            for s, d, n in dev["ops"]:
                if s >= self.lo and s + d <= self.hi:
                    out[n] += d / 1e9
        return [[n, t] for n, t in
                sorted(out.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list:
        """The ``k`` longest spans of the window in which the first device
        ran nothing, each named by the innermost ``perfbench.*`` host span
        around its middle: ``[[host span, seconds], ...]``."""
        busy = next(iter(self._busy.values()))
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        host = [e for e in self.events["host"] if e[2] != WINDOW_SPAN]
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) / 2
            around = [h for h in host if h[0] <= mid <= h[0] + h[1]]
            name = min(around, key=lambda h: h[1])[2] if around \
                else "outside any span"
            out.append([name, (e - s) / 1e9])
        return out
