"""Reduce a JAX profiler trace to device busy time, per-program and
per-kernel device time, and the longest idle gaps named by host spans.

    python3 bench/trace_reduce.py <trace dir or .xplane.pb[.gz]>

prints the planes and lines of a trace and a sample of each line's events:
the way to see what a trace holds before writing against it.

Definitions (one chip or several; device numbers are averaged over chips):

* busy: the union of the intervals in which an operation ran on the
  device (the ops line of each device plane), inside the window;
* idle share: 1 - busy / window;
* a program's device time: the busy time inside that program's executions
  (the modules line), with its execution count;
* a kernel's device time: the summed durations of the op events inside
  the window whose name or HLO names hold the kernel's name, with their
  count;
* a program's and a kernel's executions count only when they lie wholly
  inside the window, so that a time per execution is of whole ones;
* idle gaps: the stretches of the window in which no op ran, longest
  first, each named by the innermost benchmark span (``bench.*``) that
  covers its middle on any host thread, else by the innermost host event
  there, else ``"no host span"``.
"""
from __future__ import annotations

import bisect
import gzip
import itertools
import pathlib
import sys

DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BENCH_SPAN = "bench."
NAME_STATS = ("hlo_op", "long_name", "tf_op", "name", "hlo_module",
              "kernel_details")


def load(path):
    """A `jax.profiler.ProfileData` from a trace directory (its newest
    ``.xplane.pb``), an ``.xplane.pb`` file, or a gzipped one."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(gzip.decompress(
            path.read_bytes()))
    return ProfileData.from_file(str(path))


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, e)
            for e in line.events]


def device_lines(pd) -> dict:
    """{device plane name: {line name: [(name, start, end, event)]}} for
    the planes that hold an ops line."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {line.name: _events(line) for line in plane.lines}
        if OPS_LINE in lines:
            out[plane.name] = lines
    return out


def host_events(pd) -> list:
    """[(name, start, end, thread)] of every host-plane event."""
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out.extend((n, s, e, line.name) for n, s, e, _ in _events(line))
    return out


def union(intervals, lo=None, hi=None) -> list:
    """Sorted disjoint union of (start, end) intervals, clipped to
    [lo, hi] when given."""
    merged = []
    for s, e in sorted(intervals):
        if lo is not None:
            s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def gaps(busy, lo, hi) -> list:
    """The stretches of [lo, hi] that `busy` (sorted, disjoint) leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def covered(busy, prefix, s, e) -> int:
    """Length of [s, e] covered by `busy` (sorted, disjoint), given
    `prefix`, the running sums of its lengths (leading 0)."""

    def upto(t):  # busy time before t
        i = bisect.bisect_right(busy, (t, float("inf")))
        return prefix[i] - (max(busy[i - 1][1] - t, 0) if i else 0)

    return upto(e) - upto(s)


def name_gap(host, t) -> str:
    """The innermost benchmark span covering time `t` on any host thread,
    else the innermost host event, else ``"no host span"``."""
    cover = [(e - s, n) for n, s, e, _ in host if s <= t <= e]
    bench = [c for c in cover if c[1].startswith(BENCH_SPAN)]
    pick = min(bench or cover, default=None)
    return pick[1] if pick else "no host span"


def op_label(name: str) -> str:
    """An op's HLO instruction name (``%fusion.3 = f32[...] fusion(...)``
    -> ``fusion.3``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_names(name: str, event) -> str:
    """The event's name and its HLO names, for kernel matching."""
    stats = _stats(event)
    return " ".join([name] + [str(stats[k]) for k in NAME_STATS if k in stats])


def reduce(pd, window=None, programs=(), kernels=(), top=10) -> dict:
    """Reduce trace `pd` over `window` (start_ns, end_ns; default: the
    extent of the device ops).

    `programs`: {label: substring of the jitted program's module name};
    `kernels`: {label: substring of the kernel's op names}.
    Returns {busy_s, window_s, devices, programs: {label: {"s", "n"}},
    kernels: {label: {"s", "n"}}, device_ops: [[name, s]], idle_gaps:
    [[name, s]]}; device times are per chip (averaged over devices).
    """
    devices = device_lines(pd)
    if not devices:
        raise ValueError("the trace holds no device ops line")
    if window is None:
        ops = [(s, e) for d in devices.values() for _, s, e, _ in d[OPS_LINE]]
        window = (min(s for s, _ in ops), max(e for _, e in ops))
    lo, hi = window
    host = host_events(pd)
    n_dev = len(devices)
    busy_ns = 0
    prog = {k: {"s": 0.0, "n": 0} for k in programs}
    kern = {k: {"s": 0.0, "n": 0} for k in kernels}
    by_op: dict[str, float] = {}
    gap_list = []
    for lines in devices.values():
        ops = [(n, s, e, ev) for n, s, e, ev in lines[OPS_LINE]
               if s < hi and e > lo]
        busy = union([(s, e) for _, s, e, _ in ops], lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        gap_list += gaps(busy, lo, hi)
        for n, s, e, ev in ops:
            short = op_label(n)
            by_op[short] = by_op.get(short, 0.0) + (min(e, hi)
                                                    - max(s, lo)) / 1e9
            if not (s >= lo and e <= hi):
                continue
            names = op_names(n, ev)
            for label, pat in kernels.items():
                if pat in names:
                    kern[label]["s"] += (e - s) / 1e9 / n_dev
                    kern[label]["n"] += 1
        prefix = [0, *itertools.accumulate(e - s for s, e in busy)]
        for n, s, e, _ in lines.get(MODULES_LINE, []):
            if not (s >= lo and e <= hi):
                continue
            for label, pat in programs.items():
                if pat in n:
                    prog[label]["s"] += covered(busy, prefix, s, e) / 1e9 \
                        / n_dev
                    prog[label]["n"] += 1
    gap_list.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_ns / 1e9 / n_dev,
        "window_s": (hi - lo) / 1e9,
        "devices": sorted(devices),
        "programs": prog,
        "kernels": kern,
        "device_ops": [[n, s / n_dev] for n, s in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name_gap(host, (s + e) / 2), (e - s) / 1e9]
                      for s, e in gap_list[:top]],
    }


def span_window(pd, name: str):
    """(start_ns, end_ns) of the first host event called `name`."""
    for n, s, e, _ in host_events(pd):
        if n == name:
            return s, e
    raise ValueError(f"no host span {name!r} in the trace")


def dump(pd, per_line: int = 5) -> None:
    """Print every plane and line with its event count and a sample."""
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for e in events[:per_line]:
                print(f"    {e.name!r} start={e.start_ns} "
                      f"dur={e.duration_ns} stats={_stats(e)}")


if __name__ == "__main__":
    dump(load(sys.argv[1]))
