"""Device trace of a steady window, and its reduction to numbers.

`Tracer` runs `jax.profiler` over `trace_s` seconds of the window,
starting `trace_after_s` into it (at 0, the trace is on before the window
opens, so it holds the first job's submission), inside a host span
``perfbench.window`` that marks the traced interval on the trace's own
clock.  `reduce()` reads the `.xplane.pb`:

  device planes   ``/device:TPU:<n>`` — op executions (line "XLA Ops") give
                  the busy union; module executions (line "XLA Modules")
                  give per-program device time and launch order;
  host plane      ``/host:CPU`` — the harness's ``perfbench.*`` marks and
                  the runtime's own events, which label idle gaps.

Intervals are clipped to the window; per-device numbers are averaged over
the devices the cell uses.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Tuple

import numpy as np

WINDOW_SPAN = "perfbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"


def mark(name: str) -> None:
    """A zero-length host event ``perfbench.<name>`` in the trace (free
    when no trace runs).  A span left open when the trace stops is lost,
    so the harness marks points, not intervals."""
    import jax

    with jax.profiler.TraceAnnotation(f"perfbench.{name}"):
        pass


class Tracer:
    """When enabled, profiles `seconds` of the window, starting `after`
    seconds into it, from a thread of its own: the window's jobs run on
    undisturbed, and the trace stays short enough that the device keeps
    every event (a v5e drops op events past a few million)."""

    def __init__(self, enabled: bool, after: float, seconds: float):
        self.enabled = enabled
        self.after, self.seconds = float(after), float(seconds)
        self.dir = None
        self.stop_s = 0.0               # how long the profiler took to stop
        self._thread = None
        self._error = None

    def start(self) -> None:
        """Start the tracing thread; with `after` 0, return only once the
        trace and its window span are on."""
        if self.enabled:
            self.dir = tempfile.mkdtemp(prefix="perfbench_trace_")
            self._on = threading.Event()
            self._thread = threading.Thread(target=self._trace,
                                            name="perfbench-tracer")
            self._thread.start()
            if self.after <= 0:
                self._on.wait()

    def _trace(self) -> None:
        import jax

        try:
            time.sleep(self.after)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                    self._on.set()
                    time.sleep(self.seconds)
            finally:
                t0 = time.perf_counter()
                jax.profiler.stop_trace()
                self.stop_s = time.perf_counter() - t0
        except Exception as e:          # reported by stop(), in the caller
            self._error = e
        finally:
            self._on.set()

    def stop(self) -> None:
        """Wait for the trace to end (it may outlast a short window)."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
            if self._error is not None:
                raise self._error

    def reduce(self, n_devices: int) -> "Reduced | None":
        if self.dir is None:
            return None
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            return reduce(paths[0], n_devices) if paths else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: np.ndarray                      # [n, 2] start, end (ns), sorted
    modules: List[Tuple[str, int, int]]  # (name, start, end), by start


@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]              # ns, on the trace's clock
    devices: List[DeviceTrace]
    host: List[Tuple[str, int, int]]     # every host event (name, s, e)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over devices."""
        return float(np.mean([union(d.ops, *self.window) for d in
                              self.devices])) * 1e-9

    def module_runs(self, key: str, whole: bool = True
                    ) -> List[List[Tuple[str, int, int]]]:
        """Per device: executions of the modules whose name holds `key`,
        wholly inside the window (`whole`) or overlapping it."""
        lo, hi = self.window
        return [[m for m in d.modules if key in m[0]
                 and ((m[1] >= lo and m[2] <= hi) if whole
                      else (m[2] > lo and m[1] < hi))]
                for d in self.devices]

    def gaps_between(self, key: str) -> List[float]:
        """Device-idle seconds between consecutive executions of the
        modules named by `key` (both at least partly in the window, the
        gap wholly in it), over every device."""
        lo, hi = self.window
        out = []
        for d, runs in zip(self.devices, self.module_runs(key, whole=False)):
            for a, b in zip(runs, runs[1:]):
                if a[2] >= lo and b[1] <= hi:
                    out.append(((b[1] - a[2]) - union(d.ops, a[2], b[1]))
                               * 1e-9)
        return out

    def marks(self, prefix: str) -> List[Tuple[str, int, int]]:
        """The harness's ``perfbench.<prefix>*`` marks in the window."""
        lo, hi = self.window
        key = "perfbench." + prefix
        return sorted((h for h in self.host if h[0].startswith(key)
                       and lo <= h[1] <= hi), key=lambda h: h[1])

    def executions(self, key: str) -> List[Tuple[float, float, bool]]:
        """First device: (start from the window's start, length, wholly
        in the window) of each execution of the modules named by `key`,
        in ms."""
        lo, hi = self.window
        return [((s - lo) * 1e-6, (e - s) * 1e-6, s >= lo and e <= hi)
                for name, s, e in self.devices[0].modules if key in name]

    def top_modules(self, n: int = 10) -> List[List]:
        """The modules that took most device time, seconds per device."""
        lo, hi = self.window
        tot: Dict[str, float] = {}
        for d in self.devices:
            for name, s, e in d.modules:
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
        k = len(self.devices)
        return [[name, t / k] for name, t in
                sorted(tot.items(), key=lambda x: -x[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest idle gaps of the first device, each labelled at its
        middle (`label`)."""
        lo, hi = self.window
        d = self.devices[0]
        gaps = []
        t = lo
        for s, e in merged(d.ops, lo, hi):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.label((s + e) // 2), (e - s) * 1e-9]
                for s, e in gaps[:n]]

    def label(self, t: int) -> str:
        """The harness's last mark before `t`, and the innermost other
        host event around it."""
        ours = [h for h in self.host if h[0].startswith("perfbench.")
                and h[0] != WINDOW_SPAN and h[1] <= t]
        other = [h for h in self.host if h[1] <= t <= h[2]
                 and not h[0].startswith("perfbench.")]
        last = max(ours, key=lambda h: h[1])[0] if ours else "-"
        inner = min(other, key=lambda h: h[2] - h[1])[0] if other else "-"
        return f"{last}/{inner}"


def merged(iv: np.ndarray, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Intervals clipped to [lo, hi] and merged, in order."""
    out: List[Tuple[int, int]] = []
    iv = iv[(iv[:, 0] < hi) & (iv[:, 1] > lo)]
    for s, e in iv:
        s, e = max(int(s), lo), min(int(e), hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def union(iv: np.ndarray, lo: int, hi: int) -> int:
    """Length (ns) of the union of intervals inside [lo, hi]."""
    return sum(e - s for s, e in merged(iv, lo, hi))


def _events(line) -> List[Tuple[str, int, int]]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce(path: str, n_devices: int) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: _events(line) for line in plane.lines}
            ops = lines.get(OPS_LINE) or [ev for evs in lines.values()
                                          for ev in evs]
            iv = np.array(sorted((s, e) for _, s, e in ops),
                          np.int64).reshape(-1, 2)
            devices.append(DeviceTrace(
                plane.name, iv, sorted(lines.get(MODULES_LINE, []),
                                       key=lambda m: m[1])))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_events(line))
    spans = [h for h in host if h[0] == WINDOW_SPAN]
    if not spans or not devices:
        raise RuntimeError(f"trace {path} holds no window span or no "
                           f"device plane")
    devices.sort(key=lambda d: int(d.name[len(DEVICE_PREFIX):]
                                   .split()[0] or 0))
    return Reduced(window=(spans[0][1], spans[0][2]),
                   devices=devices[:n_devices], host=host)
