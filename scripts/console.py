#!/usr/bin/env python3
"""Live fleet console: one terminal pane over the observability plane.

    python scripts/console.py --obs 127.0.0.1:9560            # live, 2s
    python scripts/console.py --obs 127.0.0.1:9560 --once     # snapshot
    python scripts/console.py --obs 127.0.0.1:9560 --logs 10  # w/ log tail

Renders the /fleet + /healthz JSON of a serve.py --obs-port daemon (or
any ObsServer): service readiness (queue depth, busy workers, draining),
the membership summary (epoch, width, suspects, open breakers), and one
row per fleet member — reachability, breaker/suspect state, served
request counters, live kernel gflops/MFU gauges, injected-SDC count —
plus the round-pipeline fill pane (pipelined attempts/jobs, achieved
depth, stage waits, per-round device-idle — parsed from /metrics; one
quiet '(off)' line when DPT_PIPELINE=0 or nothing pipelined yet), the
/autoscale controller pane (targets, per-class queue depth, last 5
decisions; one quiet '(off)' line when DPT_AUTOSCALE=0) and an
optional tail of the structured log ring (/logs). Plain ANSI,
no curses: works over any ssh session, and --once makes it scriptable
(the loadgen soak and tests use it as the "can an operator actually see
the fleet" check)."""

import argparse
import json
import sys
import time
import urllib.request


def _get(base, path, timeout=5):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return json.loads(r.read())


def _get_text(base, path, timeout=5):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.read().decode()


def _pipeline_pane(base):
    """Round-pipeline fill pane, parsed off the Prometheus exposition
    (/metrics is the only surface that carries the dpt_pipeline* family),
    with the device ledger's account of what the chip waited for beside
    it (dpt_device_unfed_s*; absent on a backend with no ledger).
    A daemon that never ran a pipelined attempt — or DPT_PIPELINE=0 —
    renders as one quiet '(off)' line."""
    try:
        text = _get_text(base, "/metrics")
    except Exception:
        return ["pipeline (off)"]
    vals = {}
    for line in text.splitlines():
        if not line.startswith(("dpt_pipeline", "dpt_pipelined",
                                "dpt_device_unfed_s", "dpt_phase_clock_s")):
            continue
        name, _, raw = line.partition(" ")
        try:
            vals[name] = float(raw)
        except ValueError:
            pass
    if not vals.get("dpt_pipelined_proves_total"):
        return ["pipeline (off)"]
    clock = vals.get("dpt_phase_clock_s_total")
    unfed = "-"
    if clock:
        by_phase = sorted(
            ((v, k[len("dpt_device_unfed_s_"):-len("_total")])
             for k, v in vals.items()
             if k.startswith("dpt_device_unfed_s_") and v > 0
             and k != "dpt_device_unfed_s_total"),
            reverse=True)
        unfed = "%.1f%% of %.0fs: %s" % (
            100.0 * vals.get("dpt_device_unfed_s_total", 0.0) / clock, clock,
            ", ".join("%s=%.3gs" % (name, v) for v, name in by_phase[:4]))
    return [
        "pipeline proves=%d jobs=%d depth=%g "
        "depth_p50=%g stage_wait_p95=%.3gs" % (
            vals.get("dpt_pipelined_proves_total", 0),
            vals.get("dpt_pipelined_jobs_total", 0),
            vals.get("dpt_pipeline_depth", 0),
            vals.get('dpt_pipeline_depth_achieved_seconds'
                     '{quantile="0.5"}', 0),
            vals.get('dpt_pipeline_stage_wait_s_seconds'
                     '{quantile="0.95"}', 0)),
        "  device_unfed(%s)" % unfed]


def _fmt_member(m):
    state = "LEFT" if m.get("left") else \
        "SUSPECT" if m.get("suspect") else \
        "OPEN" if not m.get("usable") else \
        "up" if m.get("reachable") else "down"
    snap = m.get("snapshot") or {}
    ctr = snap.get("counters") or {}
    gauges = snap.get("gauges") or {}
    served = sum(v for k, v in ctr.items() if k.startswith("served_"))
    kernels = ", ".join(
        f"{k[len('kernel_'):-len('_gflops')]}={v:g}"
        for k, v in sorted(gauges.items())
        if k.startswith("kernel_") and k.endswith("_gflops"))
    return (f"  [{m['index']:>2}] {m.get('addr', '?'):<21} {state:<7} "
            f"served={served:<6} sdc={snap.get('sdc_injected', 0):<3} "
            f"epoch={snap.get('epoch', '?'):<3} "
            f"gflops({kernels or '-'})")


def _autoscale_pane(base):
    """Controller pane: targets, per-class queue depth, the last 5
    decisions. A 404 (DPT_AUTOSCALE=0 / unattached) renders as one
    quiet '(off)' line so the console works against any daemon."""
    try:
        a = _get(base, "/autoscale")
    except Exception:
        return ["autoscale (off)"]
    b, t, cd = a.get("bounds") or {}, a.get("targets") or {}, \
        a.get("cooldowns") or {}
    st = a.get("streaks") or {}
    lines = [
        f"autoscale mode={a.get('mode')} workers={a.get('workers')} "
        f"bounds={b.get('min_workers')}..{b.get('max_workers')} "
        f"up@{t.get('up_queue_per_worker')}/worker "
        f"p95_slo={t.get('slo_p95_standard_s')} "
        f"streak(up={st.get('up')},down={st.get('down')}) "
        f"cooldown(up={cd.get('up_remaining_s')}s,"
        f"down={cd.get('down_remaining_s')}s)"]
    q = a.get("queue") or {}
    by = q.get("by_class") or {}
    lines.append("  queue  depth=%s  %s" % (
        q.get("depth"),
        " ".join(f"{c}={by.get(c, 0)}"
                 for c in ("flagship", "standard", "batch"))))
    for d in (a.get("last_decisions") or [])[-5:]:
        ts = time.strftime("%H:%M:%S", time.localtime(d.get("ts", 0)))
        lines.append(f"  {ts} [{d.get('action')}] "
                     f"applied={d.get('applied')} {d.get('reason', '')}")
    return lines


def render(base, log_tail=0):
    lines = []
    h = _get(base, "/healthz")
    flt = h.get("fleet")
    lines.append(f"service  ok={h.get('ok')} uptime={h.get('uptime_s')}s "
                 f"queue={h.get('queue_depth')} "
                 f"busy={h.get('busy_workers')} "
                 f"draining={h.get('draining')}")
    by_kind = h.get("jobs_by_kind") or {}
    if by_kind:
        # circuit-zoo pane: per-kind job table + built-aggregate count
        kinds = " ".join(
            "%s(%s)" % (k, ",".join(f"{s}={n}"
                                    for s, n in sorted(v.items())))
            for k, v in sorted(by_kind.items()))
        lines.append(f"circuits {kinds} "
                     f"aggregates={h.get('aggregates', 0)}")
    if flt:
        lines.append(f"fleet    epoch={flt['epoch']} width={flt['width']} "
                     f"usable={flt['usable']} suspects={flt['suspects']} "
                     f"breakers_open={flt['breakers_open']}")
        try:
            fl = _get(base, "/fleet")
            for m in fl.get("members", []):
                lines.append(_fmt_member(m))
        except Exception as e:  # /fleet needs attach_fleet; say so once
            lines.append(f"  (no /fleet snapshot: {e})")
    else:
        lines.append("fleet    (none attached)")
    lines.extend(_pipeline_pane(base))
    lines.extend(_autoscale_pane(base))
    if log_tail:
        try:
            lg = _get(base, f"/logs?limit={log_tail}")
            lines.append(f"logs     (last {log_tail} of seq "
                         f"{lg.get('seq')})")
            for e in lg.get("events", []):
                ts = time.strftime("%H:%M:%S",
                                   time.localtime(e.get("ts", 0)))
                extra = {k: v for k, v in e.items()
                         if k not in ("ts", "seq", "level", "subsystem",
                                      "event", "proc", "pid")}
                lines.append(f"  {ts} {e.get('level', '?'):<5} "
                             f"{e.get('subsystem', '?')}/"
                             f"{e.get('event', '?')} {extra}")
        except Exception as e:
            lines.append(f"logs     (unavailable: {e})")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--obs", required=True,
                    help="host:port of the ObsServer (serve.py banner's "
                         "'obs' field)")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--once", action="store_true",
                    help="print one snapshot and exit (scriptable)")
    ap.add_argument("--logs", type=int, default=0, metavar="N",
                    help="also tail the last N structured log events")
    args = ap.parse_args()
    base = f"http://{args.obs}"
    if args.once:
        print(render(base, log_tail=args.logs))
        return 0
    try:
        while True:
            frame = render(base, log_tail=args.logs)
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
