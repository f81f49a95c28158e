"""From a jax.profiler trace to numbers: device-busy union, the device
operations that took most time, the longest idle gaps.

The arithmetic (union, gaps, top) works on plain interval lists and is
tested on hand-built ones. Only load() touches jax, and only its
ProfileData reader; run it in a process of its own (`python
harness/trace_reduce.py <trace dir> <out.json>`) so that the benchmark's
parent stays off JAX. Kernel names are printed as the trace has them: the
program has no stable ones yet.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from array import array

import numpy as np

# lines of a TPU device plane that hold executed work; "Steps" and the
# like are groupings of the same time, not more of it
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


class Events:
    """Named intervals as arrays: a second of a busy chip is hundreds of
    thousands of HLO instructions, each named by its whole HLO text, so a
    trace is held as one string per distinct name and three numbers an
    event, never as a Python object an event."""

    def __init__(self, names: list[str], ids, start, end) -> None:
        self.names = names
        self.ids = np.asarray(ids, dtype=np.int64)
        self.start = np.asarray(start, dtype=np.float64)
        self.end = np.asarray(end, dtype=np.float64)

    @classmethod
    def of(cls, named) -> "Events":
        """From [(name, start s, end s)], or an Events as it is."""
        if isinstance(named, Events):
            return named
        named = list(named)
        index: dict[str, int] = {}
        ids = [index.setdefault(n, len(index)) for n, _, _ in named]
        return cls(list(index), ids, [s for _, s, _ in named],
                   [e for _, _, e in named])

    @classmethod
    def join(cls, parts: list["Events"]) -> "Events":
        index: dict[str, int] = {}
        ids = [np.asarray([index.setdefault(n, len(index))
                           for n in p.names], dtype=np.int64)[p.ids]
               for p in parts if len(p)]
        if not ids:
            return cls([], [], [], [])
        return cls(list(index), np.concatenate(ids),
                   np.concatenate([p.start for p in parts if len(p)]),
                   np.concatenate([p.end for p in parts if len(p)]))

    def __len__(self) -> int:
        return len(self.ids)

    def renamed(self, fn) -> "Events":
        """The events under fn(name); those it names None are dropped."""
        new = [fn(n) for n in self.names]
        index: dict[str, int] = {}
        to = np.asarray([-1 if n is None else index.setdefault(n, len(index))
                         for n in new], dtype=np.int64)
        ids = to[self.ids] if len(self) else self.ids
        keep = ids >= 0
        return Events(list(index), ids[keep], self.start[keep],
                      self.end[keep])

    def totals(self) -> list[tuple[str, float]]:
        """[(name, summed seconds)], most first."""
        if not len(self):
            return []
        tot = np.bincount(self.ids, weights=self.end - self.start,
                          minlength=len(self.names))
        return [(self.names[i], float(tot[i]))
                for i in np.argsort(-tot, kind="stable")]


def _cover(start, end):
    """Disjoint sorted cover of the intervals, as (starts, ends)."""
    start = np.asarray(start, dtype=np.float64)
    end = np.asarray(end, dtype=np.float64)
    keep = end > start
    start, end = start[keep], end[keep]
    if not len(start):
        return start, end
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    reach = np.maximum.accumulate(end)
    first = np.ones(len(start), dtype=bool)
    first[1:] = start[1:] > reach[:-1]
    last = np.append(np.flatnonzero(first)[1:] - 1, len(start) - 1)
    return start[first], reach[last]


def _gaps(start, end, t0: float, t1: float):
    us, ue = _cover(start, end)
    inside = (ue > t0) & (us < t1)
    us, ue = us[inside], ue[inside]
    gs = np.maximum(np.concatenate([[t0], ue]), t0)
    ge = np.minimum(np.concatenate([us, [t1]]), t1)
    keep = ge > gs
    return gs[keep], ge[keep]


def _pairs(intervals):
    a = np.asarray(list(intervals), dtype=np.float64).reshape(-1, 2)
    return a[:, 0], a[:, 1]


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint sorted cover of the [(start, end)] intervals."""
    us, ue = _cover(*_pairs(intervals))
    return list(zip(us.tolist(), ue.tolist()))


def _busy(start, end, t0: float, t1: float) -> float:
    us, ue = _cover(start, end)
    part = np.minimum(ue, t1) - np.maximum(us, t0)
    return float(part[part > 0].sum())


def busy_seconds(intervals, t0: float, t1: float) -> float:
    """Seconds of [t0, t1] covered by the intervals."""
    return _busy(*_pairs(intervals), t0, t1)


def gaps(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    """Maximal sub-intervals of [t0, t1] in which nothing ran."""
    gs, ge = _gaps(*_pairs(intervals), t0, t1)
    return list(zip(gs.tolist(), ge.tolist()))


# HLO instructions that only hold other instructions: their time is their
# body's, which the trace lists too
_CONTAINERS = ("while", "conditional", "call")


def short_op(text: str) -> str | None:
    """`%fusion.47 = s32[..] fusion(...)` -> `fusion.47 fusion`; None for
    a container. A module name loses its fingerprint:
    `jit_bfs_dist(653..)` -> `jit_bfs_dist`."""
    if " = " not in text:
        return text.split("(")[0]
    name, rest = text.split(" = ", 1)
    # the instruction's kind: the first lower-case word before a "(" that
    # follows a blank (layouts such as {0:T(1024)} have no blank there)
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest)
    kind = m.group(1) if m else ""
    if kind in _CONTAINERS:
        return None
    if kind == "custom-call" and "custom_call_target=" in text:
        kind = text.split('custom_call_target="')[1].split('"')[0]
    return f"{name.lstrip('%')} {kind}".strip()


def device_ops(modules, ops, n: int = 10):
    """The breakdown's device_ops: whole programs first (their times add
    up to the busy time), then the leaf instructions inside them."""
    progs = Events.of(modules).renamed(short_op).totals()[: n // 2]
    leaves = Events.of(ops).renamed(short_op).totals()[: n - len(progs)]
    return [(f"program {k}", v) for k, v in progs] + \
        [(f"op {k}", v) for k, v in leaves]


def name_gaps(gap_start, gap_end, host, ops, n: int = 10):
    """The n longest gaps, each named by the host event that covers most
    of it (what the host was doing while the device had nothing) and by
    the device operation it followed."""
    host, ops = Events.of(host), Events.of(ops)
    by_end = np.argsort(ops.end, kind="stable")
    ends = ops.end[by_end]
    out = []
    for i in np.argsort(gap_start - gap_end, kind="stable")[:n]:
        s, e = float(gap_start[i]), float(gap_end[i])
        best = "no host event"
        if len(host):
            cover = np.minimum(e, host.end) - np.maximum(s, host.start)
            j = int(np.argmax(cover))
            if cover[j] > 0:
                best = host.names[host.ids[j]]
        k = int(np.searchsorted(ends, s + 1e-9, side="right")) - 1
        after = f" after {ops.names[ops.ids[by_end[k]]]}" if k >= 0 else ""
        out.append((f"host: {best}{after}"[:120], e - s))
    return out


def reduce(devices: dict, host, t0: float, t1: float,
           modules: dict | None = None) -> dict:
    """devices: per device plane its op events ([(name, start s, end s)]
    or Events); modules: per device plane its whole-program events."""
    if not devices:
        return {"window_s": t1 - t0, "device_planes": 0}
    planes = [Events.of(ev) for ev in devices.values()]
    busy = [_busy(ev.start, ev.end, t0, t1) for ev in planes]
    fullest = max(planes, key=lambda ev: float((ev.end - ev.start).sum()))
    gs, ge = _gaps(fullest.start, fullest.end, t0, t1)
    leaf = fullest.renamed(lambda nm: short_op(nm) or nm.split(" = ")[0])
    return {"window_s": t1 - t0, "device_planes": len(planes),
            "busy_s": sum(busy) / len(busy),
            "busy_s_per_device": busy,
            "device_ops": device_ops(
                Events.join([Events.of(ev)
                             for ev in (modules or {}).values()]),
                Events.join(planes)),
            "idle_gaps": name_gaps(gs, ge, host, leaf)}


def load(trace_dir: str):
    """(devices, modules, host, description) from the newest .xplane.pb
    under trace_dir; times in seconds on the trace's own clock."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices, modules, host, desc = {}, {}, [], []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU")
        for line in plane.lines:
            index: dict[str, int] = {}
            ids, t_ns, d_ns = array("q"), array("q"), array("q")
            for e in line.events:
                ids.append(index.setdefault(e.name, len(index)))
                t_ns.append(int(e.start_ns))
                d_ns.append(int(e.duration_ns))
            start = np.asarray(t_ns, dtype=np.float64) * 1e-9
            ev = Events(list(index), np.asarray(ids, dtype=np.int64), start,
                        start + np.asarray(d_ns, dtype=np.float64) * 1e-9)
            desc.append({"plane": plane.name, "line": line.name,
                         "events": len(ev), "names": sorted(index)[:12]})
            if is_dev and line.name in OP_LINES:
                devices.setdefault(plane.name, []).append(ev)
            elif is_dev and line.name in MODULE_LINES:
                modules.setdefault(plane.name, []).append(ev)
            elif plane.name.startswith("/host:") and len(ev):
                host.append(ev)
    return ({k: Events.join(v) for k, v in devices.items()},
            {k: Events.join(v) for k, v in modules.items()},
            Events.join(host), desc)


def main(argv) -> int:
    trace_dir, out = argv[1], argv[2]
    devices, modules, host, desc = load(trace_dir)
    res = {"description": desc}
    if devices:
        t0 = min(float(ev.start.min()) for ev in devices.values())
        t1 = max(float(ev.end.max()) for ev in devices.values())
        if len(host):           # the session, as far as any event shows it
            t0 = min(t0, float(host.start.min()))
            t1 = max(t1, float(host.end.max()))
        res.update(reduce(devices, host, t0, t1, modules))
        res["t0"], res["t1"] = t0, t1
    else:
        res.update({"device_planes": 0, "events": len(host)})
    with open(out, "w") as f:
        json.dump(res, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
