"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device time per kernel, the device's busy time (the union
of the intervals in which an operation ran), the longest idle gaps and
what the host was doing in each.

It reads the trace with ``jax.profiler.ProfileData`` alone; nothing here
touches a device. A device plane is one named ``/device:TPU:<n>``; its
operations are the events of its ``XLA Ops`` line, each named by its
HLO text (``%fusion.3 = f32[...] fusion(...)``), and an operation goes
by the name before `` = ``. A kernel is the ``tpu_custom_call`` op
named after the jitted core that holds it (``costs.KERNELS``). The
host's spans are the events of the ``/host:CPU`` plane whose name starts
with ``bench.`` (the benchmark's own ``TraceAnnotation``s around its
calls into the service). Times in the trace are nanoseconds on one
clock for every plane.
"""

from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPAN_PREFIX = "bench."
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(hlo: str) -> str:
    """An operation's name from its HLO text: ``%fusion.3 = ...`` ->
    ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def kernel_of(hlo: str, kernel_kinds: dict) -> str | None:
    """The kernel an operation is, if it is a ``tpu_custom_call`` named
    after one of ``kernel_kinds``' jitted cores."""
    if "tpu_custom_call" not in hlo:
        return None
    name = op_name(hlo)
    for kernel in kernel_kinds:
        if name == kernel or name.startswith(kernel + "."):
            return kernel
    return None


@dataclasses.dataclass
class Summary:
    """One traced window, per device plane averaged where it says so."""

    window_s: float                  # the traced window's length
    busy_s: float                    # union of op intervals, mean over chips
    ops: dict                        # op name -> total device seconds
    kernels: dict                    # kernel name -> (calls, device seconds)
    gaps: list                       # [(label, seconds)], the longest
    kernel_kinds: dict               # kernel name -> 'enc' | 'dec'

    def kernel(self, kind: str) -> tuple[int, float]:
        calls, secs = 0, 0.0
        for name, (c, s) in self.kernels.items():
            if self.kernel_kinds.get(name) == kind:
                calls, secs = calls + c, secs + s
        return calls, secs

    def us_per_row(self, kind: str, dispatch) -> float | None:
        """Kernel device time per launched row. The rows are the window's
        launches of that kind, scaled to the kernel calls the trace
        holds (a launch at the window's edge may fall outside it)."""
        calls, secs = self.kernel(kind)
        launches = [d for d in dispatch if d.kind == kind]
        if not calls or not launches:
            return None
        rows = sum(d.bucket for d in launches) * calls / len(launches)
        return 1e6 * secs / rows

    def roofline(self, kind: str, dispatch, n: int, limbs: int,
                 bytes_per_s: float) -> float | None:
        """Share of the bytes bound: least time the chip's HBM needs for
        the bytes the launches must move, over the kernel's device
        time (%)."""
        import costs
        calls, secs = self.kernel(kind)
        launches = [d for d in dispatch if d.kind == kind]
        if not calls or not launches or secs <= 0:
            return None
        moved = sum(costs.call_bytes(kind, d.bucket, n, limbs)
                    for d in launches) * calls / len(launches)
        return 100.0 * moved / bytes_per_s / secs

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


def reduce(path: str, kernel_kinds: dict, window_s: float | None = None):
    """Summary of one ``.xplane.pb``. ``kernel_kinds`` maps each kernel's
    name to its kind. ``window_s`` is the traced window's length; where
    it is not given, the span from the first to the last device event."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append([
                Event(kernel_of(e.name, kernel_kinds) or op_name(e.name),
                      e.start_ns, e.duration_ns)
                for ln in plane.lines if ln.name == OPS_LINE
                for e in ln.events])
        elif plane.name == HOST_PLANE:
            host += [Event(e.name, e.start_ns, e.duration_ns)
                     for ln in plane.lines for e in ln.events
                     if e.name.startswith(HOST_SPAN_PREFIX)]
    return summarize(devices, host, kernel_kinds, window_s)


def summarize(devices, host, kernel_kinds: dict,
              window_s: float | None = None) -> Summary:
    """The reduction itself, from device op events (one list per device)
    and the host's spans."""
    devices = [d for d in devices if d]
    if not devices:
        raise ValueError("the trace holds no device operation")
    if window_s is None:
        start = min(e.start_ns for d in devices for e in d)
        end = max(e.end_ns for d in devices for e in d)
        window_s = (end - start) / 1e9
    ops: dict[str, float] = {}
    kernels: dict[str, list] = {}
    busy, gaps = 0.0, []
    for events in devices:
        merged = union((e.start_ns, e.end_ns) for e in events)
        busy += sum(e - s for s, e in merged) / 1e9
        for e in events:
            ops[e.name] = ops.get(e.name, 0.0) + e.dur_ns / 1e9
            if e.name in kernel_kinds:
                c = kernels.setdefault(e.name, [0, 0.0])
                c[0] += 1
                c[1] += e.dur_ns / 1e9
        gaps += [(e0, s1) for (_s0, e0), (s1, _e1) in zip(merged,
                                                          merged[1:])]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    n = len(devices)
    return Summary(window_s=window_s, busy_s=busy / n,
                   ops={k: v / n for k, v in ops.items()},
                   kernels={k: (c, s) for k, (c, s) in kernels.items()},
                   gaps=[(_label(host, s, e), (e - s) / 1e9)
                         for s, e in longest],
                   kernel_kinds=dict(kernel_kinds))


def _label(host, start: int, end: int) -> str:
    """The host span that overlaps the gap [start, end) the most."""
    best, best_overlap = "unannotated", 0
    for e in host:
        overlap = min(e.end_ns, end) - max(e.start_ns, start)
        if overlap > best_overlap:
            best, best_overlap = e.name, overlap
    return best


def reduce_dir(trace_dir: str, kernel_kinds: dict,
               window_s: float | None = None) -> Summary:
    """Summary of the one trace ``jax.profiler`` wrote under a
    directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {paths}")
    return reduce(paths[0], kernel_kinds, window_s)
