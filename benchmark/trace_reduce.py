"""From the profiler's ``.xplane.pb`` to numbers: device events by name, the
union of the intervals in which an operation ran (busy), the idle gaps and
what the host was doing in each.

The trace is read with ``jax.profiler.ProfileData`` and nothing else.  A
device plane is one named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed HLO operation (a Pallas kernel appears under the name
given to ``pallas_call``), its line ``XLA Modules`` one event per executed
program.  Host planes hold the ``TraceAnnotation`` spans of the benchmark's
own files.  Everything is clipped to the span that marks the measured window.

    python3 -m benchmark.trace_reduce DIR            # describe a trace by hand
"""

from __future__ import annotations

import bisect
import glob
import os
import sys
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 20_000            # shorter gaps are the device's own dispatch


def short_name(text: str) -> str:
    """The trace names a device operation by its whole HLO line,
    ``%fusion.5 = bf16[2,4096]{...} fusion(...)``: keep the instruction's
    name and the shape it produces, ``fusion.5 bf16[2,4096]``.  A Pallas
    kernel's instruction carries the name given to ``pallas_call``."""
    if not text.startswith("%") or " = " not in text:
        return text
    name, rest = text[1:].split(" = ", 1)
    shape = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0]
    return f"{name} {shape}"


class Event:
    __slots__ = ("name", "start", "end")

    def __init__(self, name, start, end):
        self.name, self.start, self.end = name, float(start), float(end)

    @property
    def dur(self):
        return self.end - self.start


def union_ns(events) -> float:
    """Total length of the union of the events' intervals."""
    return sum(e - s for s, e in merged(events))


def merged(events):
    out = []
    for ev in sorted(events, key=lambda e: e.start):
        if out and ev.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], ev.end)
        else:
            out.append([ev.start, ev.end])
    return out


def clip(events, t0, t1):
    return [Event(e.name, max(e.start, t0), min(e.end, t1))
            for e in events if e.end > t0 and e.start < t1]


def sums_by_name(events) -> dict:
    out = defaultdict(float)
    for e in events:
        out[e.name] += e.dur
    return dict(out)


def gaps_of(events, t0, t1):
    """The idle intervals of [t0, t1]: what the union leaves uncovered."""
    out, at = [], t0
    for s, e in merged(events):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1))
    return out


def host_span_at(spans, s, e):
    """The host span that covers most of [s, e], the shortest on a tie (the
    innermost); its name, or a placeholder."""
    best, best_key = None, None
    for sp in spans:
        cover = min(sp.end, e) - max(sp.start, s)
        if cover <= 0:
            continue
        key = (round(cover / (e - s), 3), -sp.dur)
        if best_key is None or key > best_key:
            best, best_key = sp, key
    return best.name if best else "(no host span)"


class Reduced:
    """One trace, clipped to its window.  Times in seconds."""

    def __init__(self, window, device_ops, device_modules, host_spans):
        self.t0, self.t1 = window
        self.window_s = (self.t1 - self.t0) / 1e9
        self.host_spans = host_spans
        # per device: clipped op and module events
        self.ops = {d: clip(ev, self.t0, self.t1)
                    for d, ev in device_ops.items()}
        self.ops = {d: ev for d, ev in self.ops.items() if ev}
        self.modules = {d: clip(ev, self.t0, self.t1)
                        for d, ev in device_modules.items() if d in self.ops}
        n = max(len(self.ops), 1)
        self.busy_s = sum(union_ns(ev) for ev in self.ops.values()) / n / 1e9

    def op_seconds(self, contains=None, inside=None) -> float:
        """Device seconds of the operations whose name contains
        ``contains`` (all, if None; under ``grad`` a kernel's instruction
        is named ``transpose_jvp_<kernel>_``), averaged over the devices
        used;
        ``inside`` keeps only operations that ran within a program whose
        name contains it."""
        total = 0.0
        for d, evs in self.ops.items():
            keep = evs
            if contains is not None:
                keep = [e for e in keep if contains in e.name]
            if inside is not None:
                mods = sorted((m for m in self.modules.get(d, [])
                               if inside in m.name), key=lambda m: m.start)
                starts = [m.start for m in mods]

                def within(e):
                    i = bisect.bisect_right(starts, e.start) - 1
                    return i >= 0 and e.start < mods[i].end
                keep = [e for e in keep if within(e)]
            total += sum(e.dur for e in keep)
        return total / max(len(self.ops), 1) / 1e9

    def _first(self, per_device: dict) -> list:
        return next(iter(per_device.values()), [])

    def module_durations(self, contains) -> list:
        """Device seconds of each execution of the programs whose name
        contains ``contains`` (first device)."""
        return [m.dur / 1e9 for m in self._first(self.modules)
                if contains in m.name]

    def idle_share(self):
        """None when no device operation was traced (nothing to read)."""
        if not self.ops:
            return None
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top=10) -> dict:
        """The device operations that took most time and the idle time by
        what the host was doing in each gap, first device."""
        evs = self._first(self.ops)
        spans = [s for s in self.host_spans
                 if s.end > self.t0 and s.start < self.t1]
        gaps = defaultdict(float)
        for s, e in gaps_of(evs, self.t0, self.t1) if evs else ():
            if e - s >= MIN_GAP_NS:
                gaps[host_span_at(spans, s, e)] += e - s

        def rank(d):
            return [[k, v / 1e9] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(sums_by_name(evs)),
                "idle_gaps": rank(gaps)}


def read_planes(path):
    """(device op events, device module events, host spans) of one file.
    Host events of the Python tracer (names starting with ``$``) are
    skipped: only named spans are of use for attribution."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    dev_ops, dev_mods, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [Event(short_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                       for e in line.events]
                (dev_ops if line.name == OPS_LINE
                 else dev_mods)[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith("$") and e.duration_ns > 0:
                        host.append(Event(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    return dev_ops, dev_mods, host


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir: str, window_span: str) -> Reduced:
    dev_ops, dev_mods, host = read_planes(find_xplane(trace_dir))
    marks = [s for s in host if s.name == window_span]
    if not marks:
        raise ValueError(f"no host span named {window_span!r} in the trace")
    mark = max(marks, key=lambda s: s.dur)
    spans = [s for s in host if s is not mark]
    return Reduced((mark.start, mark.end), dev_ops, dev_mods, spans)


def describe(trace_dir: str, out=sys.stdout) -> None:
    """Planes, lines and the heaviest names of each, to read by hand."""
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(trace_dir))
    for plane in data.planes:
        print(f"PLANE {plane.name}", file=out)
        for line in plane.lines:
            evs = list(line.events)
            named = [e for e in evs if not e.name.startswith("$")]
            if not named:
                print(f"  LINE {line.name}: {len(evs)} events (python)",
                      file=out)
                continue
            tot = defaultdict(float)
            cnt = defaultdict(int)
            for e in named:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
            span = (min(e.start_ns for e in named),
                    max(e.start_ns + e.duration_ns for e in named))
            print(f"  LINE {line.name}: {len(named)} events, "
                  f"{span[0]:.0f}..{span[1]:.0f} ns", file=out)
            for k in sorted(tot, key=lambda k: -tot[k])[:25]:
                print(f"    {tot[k] / 1e6:10.3f} ms  x{cnt[k]:<6} {k[:110]}",
                      file=out)
            if plane.name.startswith(DEVICE_PREFIX) and named:
                e = named[len(named) // 2]
                print("    stats of one event:",
                      [(k, str(v)[:80]) for k, v in e.stats][:12], file=out)


if __name__ == "__main__":
    describe(sys.argv[1])
