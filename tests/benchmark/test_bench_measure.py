"""The work model against hand-computed counts, the trace reduction on a
tiny synthetic event list, and the generic readers."""

import os
from types import SimpleNamespace as NS

import pytest

from bench_toy import REPO
from benchmark.lib import manifest as M, peaks, readers, tracered as T, work

SIZES_2P13 = {"domain_size": 8192, "quotient_domain_size": 65536}
V5E = peaks.peaks_for("TPU v5 lite")


def test_work_model_at_2p13_by_hand():
    # 13 commitments: 5 wires + 5 quotient parts + 2 openings of n + 2
    # coefficients and the permutation product of n + 3
    points = 12 * 8194 + 8195
    assert points == 106523
    msm = work.family_work("msm", SIZES_2P13)
    # 32 windows x 11 Fq muls a point, 3 bands of 48 x 48 MACs x 2 a mul
    assert msm["flops"] == points * 352 * 13824 == 518346031104
    assert msm["bytes"] == points * (96 + 32) == 13634944
    # 7 transforms at n (5 wires, product, public input), 26 at 8n
    ntt = work.family_work("ntt", SIZES_2P13)
    assert ntt["flops"] == (7 * 4096 * 13 + 26 * 32768 * 16) * 6144 \
        == 86041952256
    assert ntt["bytes"] == (7 * 8192 + 26 * 65536) * 64 == 112721920
    secs, which = work.least_seconds(msm, V5E)
    assert which == "flops" and secs == pytest.approx(518346031104 / 197e12)
    assert work.least_seconds({"flops": 1e6, "bytes": 1e9}, V5E) == (
        pytest.approx(1e9 / 819e9), "bytes")
    both = work.family_work("prove", SIZES_2P13)
    assert both == {"flops": msm["flops"] + ntt["flops"],
                    "bytes": msm["bytes"] + ntt["bytes"]}
    # the counts follow the configuration's sizes and nothing else
    big = work.family_work("msm", {"domain_size": 16384})
    assert big["flops"] == (12 * 16386 + 16387) * 352 * 13824
    with pytest.raises(ValueError):
        work.family_work("pairing", SIZES_2P13)


def test_work_model_is_the_programs_arithmetic():
    """The copies agree with the originals for as long as those stay."""
    trace = pytest.importorskip("distributed_plonk_tpu.trace")
    for n in (16, 8192, 65536):
        assert work.ntt_flops(n, 3) == trace.ntt_flops(n, 3)
        assert work.msm_flops(n + 2, 5) == trace.msm_flops(n + 2, 5)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
    assert V5E["flops_per_s"] == 197e12 and V5E["bytes_per_s"] == 819e9


DEV, HOST = "/device:TPU:0", "/host:CPU"


def _events():
    ms = 1_000_000
    E = T.Event
    return [
        # host thread spans the whole stretch: 0 .. 100 ms
        E(HOST, "worker", "round1", 0, 40 * ms),
        E(HOST, "worker", "commit_wires", 20 * ms, 20 * ms),
        E(HOST, "worker", "round3", 40 * ms, 60 * ms),
        E(HOST, "worker", "unrelated_runtime_thing", 0, 100 * ms),
        # device programs: msm 10..30, ntt 50..60 and 58..70 (they overlap)
        E(DEV, T.MODULES_LINE, "jit_bucket_planes(12)", 10 * ms, 20 * ms),
        E(DEV, T.MODULES_LINE, "jit_ntt_core(7)", 50 * ms, 10 * ms),
        E(DEV, T.MODULES_LINE, "jit_ntt_core(8)", 58 * ms, 12 * ms),
    ]


def test_busy_union_and_idle_share():
    assert T.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    b = T.busy(_events())
    # busy 10..30 and 50..70 of a 100 ms stretch that starts and ends idle
    assert b["busy_s"] == pytest.approx(0.040)
    assert b["window_s"] == pytest.approx(0.100)
    assert b["idle_share"] == pytest.approx(0.60)
    assert T.busy([e for e in _events() if e.plane == HOST]) is None
    # a share is taken on one clock: with the stretch marked in the trace
    # (12..44 ms) the 40 ms of programs the profiler caught around it read
    # as the 18 ms of them inside it, and never as more than its 32 ms
    marked = T.cut(_events() + [_mark(12, 32)])
    inside = T.busy(marked)
    assert inside["window_s"] == pytest.approx(0.032)
    assert inside["busy_s"] == pytest.approx(0.018)
    assert 0 <= inside["idle_share"] == pytest.approx(1 - 0.018 / 0.032)


def _mark(start_ms, dur_ms):
    """The harness's annotation around a stretch's sleep, on a host thread."""
    return T.Event(HOST, "main", T.MARK, start_ms * 1_000_000,
                   dur_ms * 1_000_000)


def _saturated(lead_ns, mark_ns, tail_ns, programs=40):
    """A stretch whose device never rests: back-to-back programs from
    `lead_ns` before the mark to `tail_ns` after it, as the profiler, which
    starts before the sleep and stops after it, hands them over."""
    total = lead_ns + mark_ns + tail_ns
    step = total // programs
    dev = [T.Event(DEV, T.MODULES_LINE, f"jit_msm_bucket_scan({i})",
                   i * step, step if i < programs - 1 else total - i * step)
           for i in range(programs)]
    return dev + [T.Event(HOST, "main", T.MARK, lead_ns, mark_ns),
                  T.Event(HOST, "worker", "round1", 0, total)]


def test_device_events_are_cut_to_the_mark_and_host_events_are_not():
    ms = 1_000_000
    events = _events() + [_mark(25, 30)]          # the stretch is 25..55 ms
    assert T.find_mark(events) == (25 * ms, 55 * ms)
    assert T.window(events) == (25 * ms, 55 * ms)
    got = T.cut(events)
    dev = [(e.name, e.start_ns, e.dur_ns) for e in got if e.plane == DEV]
    # the msm began before the mark (10..30 -> 25..30), the first ntt ends
    # after it (50..60 -> 50..55), the second (58..70) is outside and goes
    assert dev == [("jit_bucket_planes(12)", 25 * ms, 5 * ms),
                   ("jit_ntt_core(7)", 50 * ms, 5 * ms)]
    assert [e for e in got if e.plane == HOST] == \
        [e for e in events if e.plane == HOST]
    b = T.busy(got)
    assert b["busy_s"] == pytest.approx(0.010)
    assert b["window_s"] == pytest.approx(0.030)
    # the breakdown and the gaps read the same cut events
    assert T.time_by_name(got) == {"jit_bucket_planes": pytest.approx(0.005),
                                   "jit_ntt_core": pytest.approx(0.005)}
    assert T.idle_gaps(got, r"^round\d") == {"round1": pytest.approx(0.020)}
    assert T.cut(got) == got                      # cut once or twice: the same


def test_a_list_without_a_mark_reads_by_its_own_span():
    ms = 1_000_000
    events = _events()
    assert T.find_mark(events) is None
    assert T.cut(events) is events
    assert T.window(events) == (0, 100 * ms)
    assert T.window([]) is None
    # a device event of the mark's name is no mark
    named = events + [T.Event(DEV, T.MODULES_LINE, T.MARK, 0, 5 * ms)]
    assert T.find_mark(named) is None


@pytest.mark.parametrize("lead_ns, mark_ns, tail_ns", [
    (0, 400_000_000, 0),                    # the profiler caught no more
    (1_000_000, 400_000_000, 1_000_000),
    # PR 33's refused stretch: 0.411753 s of device intervals in a trace
    # whose sleep was 0.400256 s long
    (5_000_000, 400_256_000, 6_497_000),
])
def test_a_stretch_busy_from_edge_to_edge_reads_its_length_and_no_more(
        lead_ns, mark_ns, tail_ns):
    events = _saturated(lead_ns, mark_ns, tail_ns)
    uncut = T.busy(events)["busy_s"]
    assert uncut == pytest.approx((lead_ns + mark_ns + tail_ns) / 1e9)
    b = T.busy(T.cut(events))
    assert b["window_s"] == mark_ns / 1e9
    assert b["busy_s"] == b["window_s"]           # to the nanosecond
    assert b["idle_share"] == 0.0
    by_name = T.time_by_name(T.cut(events))
    assert sum(by_name.values()) == pytest.approx(b["window_s"])
    assert T.idle_gaps(T.cut(events), r"^round\d") == {}


def test_three_saturated_stretches_are_no_busier_than_they_are_long():
    cuts = [T.cut(_saturated(lead, mark, tail)) for lead, mark, tail in
            [(1_559_000, 400_605_000, 0), (11_497_000, 400_256_000, 0),
             (700_000, 400_478_000, 805_000)]]
    stretches = [(ev, T.busy(ev)["window_s"]) for ev in cuts]
    b = T.busy_over(stretches)
    assert b["window_s"] == pytest.approx(1.201339)
    assert 0 < b["busy_s"] <= b["window_s"]
    assert b["idle_share"] == pytest.approx(0.0, abs=1e-12)
    assert b["idle_share"] >= 0
    # two planes, one busy throughout and one half of the time: the mean
    half = [e._replace(plane="/device:TPU:1", dur_ns=e.dur_ns // 2)
            for e in cuts[0] if e.plane == DEV]
    two = T.busy(cuts[0] + half)
    assert two["planes"] == 2
    assert two["busy_s"] == pytest.approx(0.75 * two["window_s"], rel=1e-6)


def _line(name, events):
    # ProfileData hands the times over as floats
    return NS(name=name, events=[NS(name=n, start_ns=float(s),
                                    duration_ns=float(d))
                                 for n, s, d in events])


def _profile(mark):
    ms = 1_000_000
    ops = [("fusion", t * ms, ms // 2) for t in range(0, 100, 2)]   # 50 ops
    host = [("round1", 0, 100 * ms)] + ([(T.MARK, *mark)] if mark else [])
    return NS(planes=[
        # the device plane comes first, as it does in a real trace
        NS(name=DEV, lines=[
            _line(T.OPS_LINE, ops), _line("Async XLA Ops", ops[:7]),
            _line(T.MODULES_LINE, [("jit_ntt_core(7)", 0, 100 * ms)])]),
        NS(name="/device:TPU:1", lines=[_line(T.OPS_LINE, ops[:20])]),
        # a host line of that name is not the device's
        NS(name=HOST, lines=[_line("main", host), _line(T.OPS_LINE, ops)])])


def test_operations_outside_the_mark_are_not_counted():
    ms = 1_000_000
    whole = T.reduce_profile(_profile(None))
    assert whole.op_events == 50 and T.find_mark(whole.events) is None
    assert whole.window_s == pytest.approx(0.100)
    assert whole.uncut_busy_s == pytest.approx(0.100)
    tr = T.reduce_profile(_profile((30 * ms, 40 * ms)))       # 30..70 ms
    # those that BEGIN inside count: 30, 32, .. 70 on the busiest plane
    assert tr.op_events == 21 and T.find_mark(tr.events) == (30 * ms, 70 * ms)
    assert tr.window_s == pytest.approx(0.040)
    assert tr.uncut_busy_s == pytest.approx(0.100)
    b = T.busy(tr.events)
    assert b["busy_s"] == b["window_s"] == tr.window_s
    assert all(isinstance(e.start_ns, int) for e in tr.events)
    # nothing in it at all: no length, no busy time, and no error
    empty = T.reduce_profile(NS(planes=[]))
    assert empty == T.Trace([], 0, 0.0, None)
    assert T.busy_over([(empty.events, empty.window_s)]) is None


def test_several_stretches_add_up():
    host_only = [e for e in _events() if e.plane == HOST]
    # 40 ms busy of 100, and a stretch with no device event at all: idle
    # for its whole 60 ms
    b = T.busy_over([(_events(), 0.100), (host_only, 0.060)])
    assert b["busy_s"] == pytest.approx(0.040)
    assert b["window_s"] == pytest.approx(0.160)
    assert b["idle_share"] == pytest.approx(0.75)
    assert T.busy_over([(host_only, 0.060)]) is None
    assert T.busy_over([]) is None
    assert T.summed([{"a": 1.0, "b": 2.0}, {"a": 0.5}]) == {"a": 1.5, "b": 2.0}


def test_family_match_and_breakdown():
    ev = _events()
    secs, names = T.family_seconds(ev, r"bucket_planes")
    assert secs == pytest.approx(0.020) and names == ["jit_bucket_planes"]
    secs, names = T.family_seconds(ev, r"ntt")
    assert secs == pytest.approx(0.022) and names == ["jit_ntt_core"]
    assert T.family_seconds(ev, r"pairing") == (0, [])
    assert T.top(T.time_by_name(ev))[0] == ["jit_ntt_core",
                                            pytest.approx(0.022)]
    # the one gap between busy intervals, 30..50 ms, has its middle at 40 ms
    # inside round1 (0..40) and commit_wires (20..40): the innermost wins
    gaps = T.idle_gaps(ev, r"^(round\d|commit_)")
    assert gaps == {"commit_wires": pytest.approx(0.020)}
    assert T.idle_gaps(ev, r"^nothing$") == {"no-host-span":
                                             pytest.approx(0.020)}


def test_readers_read_or_return_nothing():
    ev = readers.Evidence(
        statuses=[{"wait_s": 1.0, "run_s": 5.0,
                   "rounds": {"round1": 2.0, "round3": 1.0}},
                  {"wait_s": 3.0, "run_s": 7.0,
                   "rounds": {"round1": 2.0, "round1_finalize": 1.0,
                              "round3": 2.0}}],
        metrics_open={"counters": {"jobs_completed": 4}},
        metrics_close={"counters": {"jobs_completed": 8, "pipelined_jobs": 2}},
        monitoring=[(1.0, "compile"), (5.0, "compile"), (5.5, "other"),
                    (9.0, "compile")],
        t_open=2.0, t_close=8.0, stretch_proofs=2,
        stretches=[(_events(), 20_000, 0.100)],
        memory_stats=[{"peak_bytes_in_use": 1_500_000_000},
                      {"peak_bytes_in_use": 1_300_000_000}],
        sizes=SIZES_2P13, peaks=V5E)
    rd = lambda **spec: readers.read(spec, ev)  # noqa: E731
    assert rd(kind="status_field", plus=["wait_s"]) == 2.0
    assert rd(kind="status_field", plus=["run_s"], minus=["rounds.*"]) == 2.0
    # the rounds named one by one, a pipelined job's finalize halves where
    # it has them: what is left of run_s keeps the checkpoint saves
    assert rd(kind="status_field", plus=["run_s"],
              minus=["rounds.round1", "rounds.round1_finalize",
                     "rounds.round3"],
              optional=["rounds.round1_finalize"]) == 2.0
    assert rd(kind="status_field", plus=["rounds.round1",
                                         "rounds.round1_finalize"],
              optional=["rounds.round1_finalize"]) == 2.5
    assert rd(kind="status_field", plus=["rounds.round9"]) is None
    assert rd(kind="service_metric", counter="pipelined_jobs",
              percent_of="jobs_completed") == 50.0
    # no batched prove created the counter: a share of jobs done, and 0
    assert rd(kind="service_metric", counter="batch_jobs",
              percent_of="jobs_completed") == 0.0
    assert rd(kind="service_metric", counter="batch_jobs",
              percent_of="no_such_base") is None
    assert rd(kind="monitoring_event", event="compile") == 1.0
    assert rd(kind="memory_stats", key="peak_bytes_in_use",
              divide_by=1e9) == 1.5
    assert rd(kind="trace_idle") == pytest.approx(60.0)
    assert rd(kind="trace_op_mean") == pytest.approx(2.0)   # 40 ms / 20,000
    assert rd(kind="trace_match", regex="bucket_planes") == pytest.approx(0.010)
    least = 518346031104 / 197e12
    assert rd(kind="roofline", family="msm", regex="bucket_planes") == \
        pytest.approx(100 * least / 0.010)
    # nothing to read is nothing, never 0
    assert rd(kind="roofline", family="msm", regex="no_such_program") is None
    blind = readers.Evidence(statuses=ev.statuses)
    specs = [{"name": "a", "unit": "%", "kind": "roofline", "family": "ntt",
              "regex": "ntt"},
             {"name": "b", "unit": "s", "kind": "status_field",
              "plus": ["wait_s"]},
             {"name": "c", "unit": "%", "kind": "trace_idle"},
             {"name": "e", "unit": "us", "kind": "trace_op_mean"},
             {"name": "d", "unit": "GB", "kind": "memory_stats",
              "key": "peak_bytes_in_use"}]
    assert readers.read_all(specs, blind) == {"b": {"value": 2.0, "unit": "s"}}
    with pytest.raises(ValueError):
        readers.read({"kind": "guess"}, ev)


def _metric_file(name):
    return dict(M.load_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                         name + ".json")), name=name, unit="%")


@pytest.mark.parametrize("name, before, after, want", [
    # every job of the window went alone, so no pipelined prove created the
    # counter: a share of the jobs that were done, and it is 0
    ("pipelined_jobs_pct", {"jobs_completed": 2}, {"jobs_completed": 7}, 0.0),
    # nothing was done in the window: no share of nothing
    ("pipelined_jobs_pct", {"jobs_completed": 2}, {"jobs_completed": 2}, None),
    ("pipelined_jobs_pct", {}, {}, None),
    # a counter that is there reads as it did
    ("pipelined_jobs_pct", {"jobs_completed": 2, "pipelined_jobs": 2},
     {"jobs_completed": 10, "pipelined_jobs": 4}, 25.0),
    ("pipelined_jobs_pct", {"jobs_completed": 2},
     {"jobs_completed": 10, "pipelined_jobs": 2}, 25.0),
    # the parent of PR 27 has neither counter: the base is absent, nothing
    ("circuit_template_hit_pct", {"jobs_completed": 2}, {"jobs_completed": 7},
     None),
])
def test_an_absent_counter_has_grown_by_0(name, before, after, want):
    spec = _metric_file(name)
    ev = readers.Evidence(metrics_open={"counters": before},
                          metrics_close={"counters": after})
    assert readers.read(spec, ev) == want
    assert readers.read_all([spec], ev) == (
        {} if want is None else {name: {"value": want, "unit": "%"}})
