"""The work model against hand-computed counts, the trace reduction on a
tiny synthetic event list, and the generic readers."""

import pytest

from bench_toy import REPO  # noqa: F401 - puts the repo on the path
from benchmark.lib import peaks, readers, tracered as T, work

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
    # nothing is cut off at 100%: a device busier than the stretch is long
    # by the host's clock reads as it is
    over = T.busy(_events(), stretch_s=0.032)
    assert over["window_s"] == 0.032
    assert over["idle_share"] == pytest.approx(1 - 0.040 / 0.032)


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
    assert rd(kind="service_metric", counter="batch_jobs",
              percent_of="jobs_completed") is None
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
