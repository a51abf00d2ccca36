"""One generic reader for each kind of per-layer metric source.

A metric is a data file `layer_metrics/<name>.json` with a `kind` and that
kind's parameters; `read(spec, evidence)` returns the number or None. A
reader that finds nothing to read returns None and the harness leaves the
metric out of the line: no reader turns "nothing" into 0.
"""

from . import tracered, work


class Evidence:
    """What a traced run has in hand once the window has closed."""

    def __init__(self, statuses=(), metrics_open=None, metrics_close=None,
                 monitoring=(), t_open=None, t_close=None, stretches=None,
                 stretch_proofs=None, memory_stats=(), sizes=None,
                 peaks=None):
        self.statuses = list(statuses)          # STATUS of the window's jobs
        self.metrics_open = metrics_open or {}  # Metrics.snapshot() at open
        self.metrics_close = metrics_close or {}
        self.monitoring = list(monitoring)      # [(t, event name)]
        self.t_open, self.t_close = t_open, t_close
        # the traced stretches, [(events cut to the stretch's mark, operations
        # counted inside it, the mark's seconds)], or None where nothing was
        # traced
        self.stretches = stretches
        self.stretch_proofs = stretch_proofs    # proofs done in the stretches
        self.memory_stats = list(memory_stats)  # one dict per device
        self.sizes = sizes or {}
        self.peaks = peaks


def _field(status, path):
    """A dotted path into a STATUS dict; `rounds.*` sums a dict's values.
    None when any step is missing."""
    cur = status
    for part in path.split("."):
        if part == "*":
            if not isinstance(cur, dict) or not cur:
                return None
            return float(sum(cur.values()))
        if not isinstance(cur, dict) or cur.get(part) is None:
            return None
        cur = cur[part]
    return float(cur)


def read_status_field(spec, ev):
    """Mean over the window's jobs of sum(plus) - sum(minus). A field listed
    under `optional` counts 0 where a job lacks it."""
    vals = []
    optional = set(spec.get("optional", ()))
    for st in ev.statuses:
        total, ok = 0.0, True
        for sign, paths in ((1, spec.get("plus", ())),
                            (-1, spec.get("minus", ()))):
            for p in paths:
                v = _field(st, p)
                if v is None:
                    if p in optional:
                        continue
                    ok = False
                    break
                total += sign * v
        if ok:
            vals.append(total)
    return sum(vals) / len(vals) if vals else None


def read_service_metric(spec, ev):
    """A counter's growth over the window, optionally as a percentage of
    another counter's growth. A program creates a counter at its first
    increment, so one that does not exist at the close has grown by 0: a
    share of a base that did grow then reads 0, and a share of a base that
    is absent or did not grow reads nothing."""
    def grown(name):
        a = ev.metrics_close.get("counters", {}).get(name)
        if a is None:
            return None
        return a - ev.metrics_open.get("counters", {}).get(name, 0)
    value = grown(spec["counter"]) or 0.0
    if "percent_of" in spec:
        base = grown(spec["percent_of"])
        return 100.0 * value / base if base else None
    return float(value)


def read_monitoring_event(spec, ev):
    """How many times the jax.monitoring event fired inside the window."""
    if ev.t_open is None or ev.t_close is None:
        return None
    return float(sum(1 for t, name in ev.monitoring
                     if name == spec["event"] and ev.t_open <= t <= ev.t_close))


def read_trace_match(spec, ev):
    """Device seconds per proof of the programs the regular expression
    finds."""
    if not ev.stretches or not ev.stretch_proofs:
        return None
    found = [tracered.family_seconds(
        events, spec["regex"], spec.get("line", tracered.MODULES_LINE))
        for events, _ops, _s in ev.stretches]
    secs = sum(f[0] for f in found)
    return secs / ev.stretch_proofs if secs > 0 else None


def read_roofline(spec, ev):
    """Least time over device time, in percent: the least time is the work
    one proof needs of this family at the chip's peaks, the device time the
    family's seconds per proof in the traced stretch."""
    per_proof = read_trace_match(spec, ev)
    if per_proof is None or ev.peaks is None:
        return None
    least, _which = work.least_seconds(
        work.family_work(spec["family"], ev.sizes), ev.peaks)
    return 100.0 * least / per_proof


def _busy(spec, ev):
    if not ev.stretches:
        return None
    return tracered.busy_over([(events, s) for events, _ops, s in ev.stretches],
                              spec.get("line", tracered.MODULES_LINE))


def read_trace_idle(spec, ev):
    """The share of the traced stretches, together, in which no program
    ran on the device. Busy time and length are both on the trace's clock,
    the device's events cut to each stretch's mark, so it reads 0 to 100:
    0 for a device busy from edge to edge of every stretch, never less."""
    b = _busy(spec, ev)
    return None if b is None else 100.0 * b["idle_share"]


def read_trace_op_mean(spec, ev):
    """Busy time over the number of operations the device ran in the traced
    stretches, in microseconds: how large one device operation is."""
    b = _busy(spec, ev)
    ops = sum(o for _e, o, _s in ev.stretches) if ev.stretches else 0
    return 1e6 * b["busy_s"] / ops if b and ops else None


def read_memory_stats(spec, ev):
    """The fullest device's reading of one memory_stats key, scaled."""
    vals = [s[spec["key"]] for s in ev.memory_stats if s and spec["key"] in s]
    return max(vals) / float(spec.get("divide_by", 1)) if vals else None


READERS = {
    "status_field": read_status_field,
    "service_metric": read_service_metric,
    "monitoring_event": read_monitoring_event,
    "trace_match": read_trace_match,
    "roofline": read_roofline,
    "trace_idle": read_trace_idle,
    "trace_op_mean": read_trace_op_mean,
    "memory_stats": read_memory_stats,
}


def read(spec, evidence):
    try:
        reader = READERS[spec["kind"]]
    except KeyError:
        raise ValueError(f"layer metric {spec.get('name')}: no reader of "
                         f"kind {spec.get('kind')!r}") from None
    return reader(spec, evidence)


def read_all(specs, evidence):
    """{name: {"value", "unit"}} of every metric that found something."""
    out = {}
    for spec in specs:
        value = read(spec, evidence)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out
