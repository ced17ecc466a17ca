"""From a profiler trace to device busy time, program time and gaps.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps three kinds of events, on one clock, in a plain :class:`Trace`:

* device operations — the ``XLA Ops`` line of each ``/device:TPU:<k>``
  plane;
* device programs — the ``XLA Modules`` line of the same planes (one
  event per execution of a compiled program);
* host spans — the named events of the ``/host:CPU`` threads that carry
  the benchmark's own ``bench.*`` annotations.

The reductions below work on that plain form, which
:meth:`Trace.to_json` / :meth:`Trace.from_json` round-trip, so they can
be checked on a small recorded trace without a chip.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import re
from pathlib import Path

Event = tuple[str, int, int]          # (name, start_ns, end_ns)

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Event]]          # device id -> operations
    modules: dict[int, list[Event]]      # device id -> program executions
    host: dict[str, list[Event]]         # host thread -> spans

    def to_json(self) -> str:
        return json.dumps({
            "ops": {str(k): v for k, v in self.ops.items()},
            "modules": {str(k): v for k, v in self.modules.items()},
            "host": self.host,
        })

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        raw = json.loads(text)

        def events(group):
            return [tuple(e) for e in group]

        return cls(
            ops={int(k): events(v) for k, v in raw["ops"].items()},
            modules={int(k): events(v) for k, v in raw["modules"].items()},
            host={k: events(v) for k, v in raw["host"].items()},
        )


def _short(name: str) -> str:
    """An XLA op event is named by its whole HLO line; keep the op name."""
    return name.split(" = ", 1)[0]


def load(path: str | Path) -> Trace:
    """Read one ``.xplane.pb`` into a :class:`Trace`.  Of the host
    threads only those that carry a ``bench.*`` span are kept."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    host: dict[str, list[Event]] = {}
    for plane in data.planes:
        match = _DEVICE_PLANE.match(plane.name)
        if match:
            dev = int(match.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        (_short(e.name), int(e.start_ns), int(e.end_ns))
                        for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.setdefault(dev, []).extend(
                        (e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = [(e.name, int(e.start_ns), int(e.end_ns))
                          for e in line.events if e.end_ns > e.start_ns]
                if any(n.startswith("bench.") for n, _, _ in events):
                    host.setdefault(line.name, []).extend(events)
    return Trace(ops=ops, modules=modules, host=host)


def find_xplane(log_dir: str | Path) -> Path:
    """The one ``.xplane.pb`` a ``jax.profiler`` session wrote."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return found[0]


def spans(trace: Trace, name: str) -> list[tuple[int, int]]:
    """Intervals of every host span called ``name``, in time order."""
    out = [(s, e) for events in trace.host.values()
           for n, s, e in events if n == name]
    return sorted(out)


def window(trace: Trace) -> tuple[int, int]:
    """The measured window: the one ``bench.window`` host span."""
    found = spans(trace, WINDOW_SPAN)
    if len(found) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(found)}")
    return found[0]


def merged(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of ``intervals`` clipped to ``[lo, hi]``, as sorted
    disjoint intervals."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace, dev: int, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi]`` in which an operation ran on ``dev``."""
    return sum(e - s for s, e in merged(
        ((s, e) for _, s, e in trace.ops.get(dev, [])), lo, hi))


def module_ns(trace: Trace, pattern: str, lo: int, hi: int) -> tuple[int, int]:
    """Summed device time and count of the program executions whose name
    contains ``pattern``, over every device, within ``[lo, hi]``."""
    total = count = 0
    for events in trace.modules.values():
        for name, s, e in events:
            if pattern in name and s >= lo and e <= hi:
                total += e - s
                count += 1
    return total, count


def top_ops(trace: Trace, devs, lo: int, hi: int, top: int = 10):
    """The ``top`` operations by summed device seconds in the window,
    averaged over ``devs``, each named ``op (program)``."""
    totals: dict[str, int] = {}
    for dev in devs:
        mods = sorted(trace.modules.get(dev, []), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, s, e in trace.ops.get(dev, []):
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and mods[k][2] >= s:
                name = f"{name} ({mods[k][0].split('(')[0]})"
            totals[name] = totals.get(name, 0) + e - s
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9 / len(devs)] for name, ns in ranked]


def _host_label(trace: Trace, t: int) -> str:
    """What the host was doing at ``t``: the innermost benchmark span,
    and the innermost other host span within it, on the same thread."""
    for events in trace.host.values():
        bench = [(s, e, n) for n, s, e in events
                 if n.startswith("bench.") and n != WINDOW_SPAN and s <= t < e]
        if not bench:
            continue
        s0, e0, label = max(bench)
        inner = [(s, e, n) for n, s, e in events
                 if not n.startswith("bench.") and s0 <= s <= t < e <= e0]
        if inner:
            label += "/" + max(inner)[2]
        return label
    return "outside bench spans"


def idle_gaps(trace: Trace, dev: int, lo: int, hi: int, top: int = 10):
    """The ``top`` longest stretches of ``[lo, hi]`` with no operation
    on ``dev``, each named by what the host was doing at its middle."""
    busy = merged(((s, e) for _, s, e in trace.ops.get(dev, [])), lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_host_label(trace, (s + e) // 2), (e - s) / 1e9]
            for s, e in gaps[:top]]
