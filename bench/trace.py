"""From a profiler trace (``.xplane.pb``) to device busy time, idle time
and device time by kind of operation.

The device's operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane. Where the trace has no such plane (a CPU run,
which the tests record), they are the host-plane events that carry an
``hlo_op`` stat. The benchmark's own host spans (``TraceAnnotation``) sit
on the host plane, on the same clock.

* busy: the union of the device's operation intervals inside the traced
  window, averaged over the devices used;
* own time: operations nest (a loop holds its body), so the time by kind
  and by operation counts each operation's time less that of the
  operations inside it;
* idle gaps: the stretches of the window with no operation running, each
  named by the host span that overlaps it most;
* kinds: ``kernel`` (custom calls: the Mosaic interaction kernel),
  ``sort`` (XLA sorts: order statistics and schedule compaction) and
  ``other``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
HOST_SPANS = ("setup", "window", "chunk_dispatch", "device_wait",
              "history_fetch")


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except Exception:  # an event without readable stats has none
        return {}


def opcode(name: str) -> str:
    """The HLO opcode of a device operation. On the TPU an event is named
    by its whole HLO instruction (``%sort.41 = (s32[..]) sort(...)``):
    the opcode is the first word before a parenthesis after the shape.
    Elsewhere (the CPU) it is the name's prefix (``sort.26``)."""
    if " = " in name:
        m = re.search(r"\s([a-z][\w\-]*)\(", name.split(" = ", 1)[1])
        if m:
            return m.group(1)
    return name.lstrip("%").split(".")[0]


def short(name: str) -> str:
    """``%sort.41 (sort)`` for a whole HLO instruction, else the name."""
    if " = " in name:
        return f"{name.split(' = ', 1)[0]} ({opcode(name)})"
    return name


def kind(name: str) -> str:
    """The kind of one device operation: ``kernel`` for custom calls (the
    Mosaic interaction kernel), ``sort`` for XLA sorts, else ``other``."""
    op = opcode(name)
    if op in ("custom-call", "custom_call"):
        return "kernel"
    if op == "sort":
        return "sort"
    return "other"


def self_times(ops: list) -> list:
    """``[(name, start, end, self_ns)]``: operations nest on one line (a
    loop holds its body), so each one's own time leaves out the time of
    the operations inside it."""
    out, stack = [], []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= e - s
        stack.append([name, s, e, e - s])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def read_events(pd) -> tuple[dict, list]:
    """``({device: [(name, start_ns, end_ns)]}, [host spans])``."""
    devices, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
            devices[plane.name] = ops
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        cpu_ops = []
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_SPANS:
                    host.append((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
                elif not devices:
                    st = _stats(e)
                    if "hlo_op" in st and e.duration_ns > 0:
                        cpu_ops.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
        if not devices and cpu_ops:
            devices[HOST_PLANE] = cpu_ops
    return devices, host


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def reduce(devices: dict, host: list, top: int = 10) -> dict:
    """Busy, idle, kinds and breakdown inside the host span ``window``."""
    spans = [h for h in host if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError("the trace has no 'window' host span")
    if not devices:
        raise ValueError("the trace holds no device operation")
    _, w0, w1 = spans[-1]
    window_ns = w1 - w0
    busy_total, kinds, by_name, gaps = 0, {}, {}, []
    labels = [h for h in host if h[0] != WINDOW_SPAN]
    for ops in devices.values():
        inside = []
        for name, s, e in ops:
            s, e = _clip(s, e, w0, w1)
            if e > s:
                inside.append((name, s, e))
        for name, _, _, own in self_times(inside):
            k = kind(name)
            kinds[k] = kinds.get(k, 0) + own
            by_name[short(name)] = by_name.get(short(name), 0) + own
        busy = _union([(s, e) for _, s, e in inside])
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gaps.append((_label(labels, gs, ge), ge - gs))
    n = len(devices)
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_total / n / 1e9,
        "kind_s": {k: v / n / 1e9 for k, v in kinds.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in gaps[:top]],
    }


def _label(spans, s, e) -> str:
    """The host span overlapping [s, e) the most; "host" where none does."""
    best, name = 0, "host"
    for n, hs, he in spans:
        ov = min(e, he) - max(s, hs)
        if ov > best:
            best, name = ov, n
    return name


def summarize(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(newest_xplane(trace_dir))
    return reduce(*read_events(pd))
