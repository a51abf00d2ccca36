"""From a profiler trace to numbers: device busy union, idle share, time by
program family, the operations that took most time, the longest idle gaps.

A traced stretch is on ONE clock, the trace's own. The harness sleeps inside
a host annotation named `MARK`; where the trace is read, the device's events
are cut to that mark's interval, once (`cut`, in `reduce_profile`), and
every number of the stretch comes from the cut events, its length from the
mark. The profiler runs a little longer than the mark on both sides, so
without the cut a saturated device would read busier than the stretch is
long (PERF.md sec. 6, PR 33).

The reduction works on plain event tuples so that it is tested on a tiny
synthetic list; `read_xspace` alone touches jax.
"""

import bisect
import re
from collections import namedtuple

Event = namedtuple("Event", "plane line name start_ns dur_ns")

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"


# a v5e runs some 1.7 million sub-microsecond operations in a second of
# these programs (my chip run, PR 25): one Python object for each is minutes
# of work, and the program-level line carries the same busy time. They are
# counted, not read.
OPS_LINE = "XLA Ops"
SKIPPED_LINES = (OPS_LINE, "Async XLA Ops")

# the host annotation a traced stretch is taken inside (harness.py::
# TraceStretches._take): its interval IS the stretch
MARK = "bench/stretch"

# events: cut to the mark; op_events: operation events that start inside it;
# window_s: the stretch's length (`window`: the mark's); uncut_busy_s: `busy`
# of the events as the profiler gave them, for the log
Trace = namedtuple("Trace", "events op_events window_s uncut_busy_s")


def find_mark(events):
    """(start_ns, end_ns) of the stretch's mark, the event named MARK
    outside the device planes (a profiler session holds one), or None."""
    for e in events:
        if e.name == MARK and not DEVICE_PLANE_RE.match(e.plane):
            return e.start_ns, e.start_ns + e.dur_ns
    return None


def window(events):
    """(start_ns, end_ns) of the stretch these events are of: the mark's
    interval, or without a mark the first event's start to the last one's
    end over ALL planes, host threads included. None without events."""
    if not events:
        return None
    return find_mark(events) or (min(e.start_ns for e in events),
                                 max(e.start_ns + e.dur_ns for e in events))


def cut(events):
    """The events cut to their mark: a device-plane event outside the
    mark's interval is dropped and one that straddles an edge is cut at
    it; host events stay whole (they only name gaps). Without a mark the
    events come back as they are."""
    mark = find_mark(events)
    if mark is None:
        return events
    lo, hi = mark
    device = set(device_planes(events))
    out = []
    for e in events:
        if e.plane in device:
            start = max(e.start_ns, lo)
            end = min(e.start_ns + e.dur_ns, hi)
            if end < start or (end == start and e.dur_ns):
                continue
            if end - start != e.dur_ns:
                e = e._replace(start_ns=start, dur_ns=end - start)
        out.append(e)
    return out


def reduce_profile(data):
    """A jax.profiler.ProfileData -> Trace: every event but those of the
    operation-level device lines, the device's cut to the mark, and how
    many operation events began inside the mark on the busiest device
    plane."""
    events, op_lines = [], []
    for plane in data.planes:
        for line in plane.lines:
            if line.name in SKIPPED_LINES:
                if line.name == OPS_LINE and DEVICE_PLANE_RE.match(plane.name):
                    op_lines.append(line)
                continue
            for ev in line.events:
                events.append(Event(plane.name, line.name, ev.name,
                                    int(ev.start_ns), int(ev.duration_ns)))
    # without a mark (no real run: the tests' lists) every operation counts
    lo, hi = find_mark(events) or (float("-inf"), float("inf"))
    op_events = max((sum(1 for ev in line.events if lo <= ev.start_ns <= hi)
                     for line in op_lines), default=0)
    w = window(events)
    return Trace(cut(events), op_events, (w[1] - w[0]) / 1e9 if w else 0.0,
                 (busy(events) or {}).get("busy_s"))


def read_xspace(serialized):
    """The Trace of a serialized XSpace, as ProfilerSession.stop() returns."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_serialized_xspace(serialized))


def device_planes(events):
    return sorted({e.plane for e in events if DEVICE_PLANE_RE.match(e.plane)})


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _busy_intervals(events, plane, line):
    return union([(e.start_ns, e.start_ns + e.dur_ns) for e in events
                  if e.plane == plane and e.line == line and e.dur_ns > 0])


def busy(events, line=MODULES_LINE):
    """{"busy_s", "window_s", "idle_share", "planes"}: seconds in which a
    program ran on the device, averaged over the device planes that ran
    any, and the stretch they are a share of (`window`), both on the
    trace's clock. A stretch that begins or ends with the device empty
    counts that time as idle. Handed events that are cut to their mark, or
    that have none, every plane's busy intervals lie inside the stretch:
    0 <= busy_s <= window_s, and a device busy from edge to edge reads
    idle 0, never less. None without a device event."""
    planes = device_planes(events)
    per_plane = {p: _busy_intervals(events, p, line) for p in planes}
    per_plane = {p: iv for p, iv in per_plane.items() if iv}
    if not per_plane:
        return None
    busy_ns = sum(sum(e - s for s, e in iv) for iv in per_plane.values())
    busy_s = busy_ns / len(per_plane) / 1e9
    start, end = window(events)
    window_s = (end - start) / 1e9
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "planes": len(per_plane)}


def busy_over(stretches, line=MODULES_LINE):
    """`busy` over several traced stretches [(events, seconds)], the
    seconds a stretch's length on its trace's clock (`Trace.window_s`):
    busy seconds and lengths summed. A stretch in which nothing ran on the
    device (its trace may hold no device plane at all) is idle for its
    whole length. None when no stretch has a device event."""
    busy_s = total_s = 0.0
    seen = False
    for events, seconds in stretches:
        b = busy(events, line)
        total_s += seconds
        if b is not None:
            busy_s += b["busy_s"]
            seen = True
    if not seen or total_s <= 0:
        return None
    return {"busy_s": busy_s, "window_s": total_s,
            "idle_share": 1.0 - busy_s / total_s}


def summed(dicts):
    """{name: seconds} added up over several {name: seconds}."""
    out = {}
    for d in dicts:
        for k, v in d.items():
            out[k] = out.get(k, 0.0) + v
    return out


def time_by_name(events, line=MODULES_LINE):
    """{name: seconds} on one line of the device planes, averaged over the
    planes; a trailing "(1234)" run id is dropped from program names."""
    out, used = {}, set()
    for e in events:
        if e.line == line and DEVICE_PLANE_RE.match(e.plane):
            name = re.sub(r"\(\d+\)$", "", e.name)
            out[name] = out.get(name, 0.0) + e.dur_ns / 1e9
            used.add(e.plane)
    return {k: v / len(used) for k, v in out.items()}


def family_seconds(events, regex, line=MODULES_LINE):
    """(seconds, matched names) of the device events whose name the regular
    expression finds, averaged over the device planes."""
    pat = re.compile(regex)
    by_name = time_by_name(events, line)
    hit = {k: v for k, v in by_name.items() if pat.search(k)}
    return sum(hit.values()), sorted(hit)


def top(by_name, k=10):
    return [[name, secs] for name, secs in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(events, host_name_re, line=MODULES_LINE):
    """The device's idle time by what the host was doing: every gap between
    busy intervals of the first device plane is named after the innermost
    host span (an event outside the device planes whose name
    `host_name_re` finds) that covers the gap's middle, and the seconds are
    summed by name: {name: seconds}."""
    planes = device_planes(events)
    if not planes:
        return {}
    iv = _busy_intervals(events, planes[0], line)
    gaps = [(a_end, b_start) for (_a, a_end), (b_start, _b)
            in zip(iv, iv[1:]) if b_start > a_end]
    pat = re.compile(host_name_re)
    spans = sorted(((e.start_ns, e.start_ns + e.dur_ns, e.name)
                    for e in events if not DEVICE_PLANE_RE.match(e.plane)
                    and e.dur_ns > 0 and pat.search(e.name)),
                   key=lambda s: s[0])
    starts = [s[0] for s in spans]
    out = {}
    for g0, g1 in gaps:
        mid = (g0 + g1) // 2
        best = None
        # spans are few (tens per proof): scan those that start before mid
        for s0, s1, name in spans[:bisect.bisect_right(starts, mid)]:
            if s1 >= mid and (best is None or s1 - s0 < best[0]):
                best = (s1 - s0, name)
        name = best[1] if best else "no-host-span"
        out[name] = out.get(name, 0.0) + (g1 - g0) / 1e9
    return out
