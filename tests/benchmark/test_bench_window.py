"""The window's arithmetic on a fake clock, and the closed-loop generator
against a fake client. No service, no timing."""

import pytest

from bench_toy import REPO  # noqa: F401 - puts the repo on the path
from benchmark.lib import window as W


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _req(win, clock, client, k, t_submit, t_result, state="done"):
    r = W.Request(client, k, {"seed": k})
    clock.t = t_submit
    win.submitting(r)
    return r, t_result, state


def test_window_sends_nothing_after_seconds_and_closes_at_the_last_result():
    clock = Clock()
    win = W.Window(clock, seconds=10.0)
    # two clients; results at 4, 9, 12 and 13: both requests that were sent
    # before 10 s count, whole, and the window is as long as they took
    plan = [_req(win, clock, 0, 0, 100.0, 104.0),
            _req(win, clock, 1, 0, 100.0, 109.0),
            _req(win, clock, 0, 1, 104.0, 112.0),
            _req(win, clock, 1, 1, 109.0, 113.0)]
    for r, t, state in sorted(plan, key=lambda p: p[1]):
        clock.t = t
        r.state, r.proof = state, b"p"
        win.arrived(r)
        assert win.wants_more(0) == (t - 100.0 < 10.0)
    win.close()
    assert win.t_open == 100.0 and win.t_close == 113.0
    s = W.summarize(win)
    assert s["attempted"] == 4 and len(win.counted()) == 4
    assert s["window_s"] == 13.0        # not the 10 s asked for
    assert s["proofs_per_s"] == pytest.approx(4 / 13.0)
    assert s["latency_mean_s"] == pytest.approx((4 + 9 + 8 + 4) / 4)
    assert s["latency_max_s"] == 9.0
    assert s["failed"] == 0


def test_failed_requests_count_as_attempted_and_failed():
    clock = Clock()
    win = W.Window(clock, seconds=5.0)
    a = _req(win, clock, 0, 0, 100.0, 103.0)[0]
    clock.t = 103.0
    a.state = "failed"
    win.arrived(a)
    b = _req(win, clock, 0, 1, 103.0, 106.0)[0]
    clock.t = 106.0
    b.state, b.proof = "done", b"p"
    win.arrived(b)
    win.close()
    s = W.summarize(win)
    assert (s["attempted"], s["failed"]) == (2, 1)
    assert s["proofs_per_s"] == pytest.approx(1 / 6.0)
    assert s["latency_max_s"] == 3.0


def test_job_seeds_are_a_function_of_the_run_seed():
    big = (1 << 31) + 12345       # more than 32 signed bits hold
    a = [W.job_seed(big, "window", c, k) for c in range(4) for k in range(8)]
    b = [W.job_seed(big, "window", c, k) for c in range(4) for k in range(8)]
    assert a == b and len(set(a)) == len(a)
    assert set(a).isdisjoint(W.job_seed(big, "warmup", c, k)
                             for c in range(4) for k in range(8))
    assert a != [W.job_seed(big + 1, "window", c, k)
                 for c in range(4) for k in range(8)]
    assert all(0 < s < 1 << 31 for s in a)
    mix = [({"kind": "a"}, 3), ({"kind": "b"}, 1)]
    kinds = [W.draw_spec(mix, 7, "window", 0, k)["kind"] for k in range(200)]
    assert 120 < kinds.count("a") < 180
    assert kinds == [W.draw_spec(mix, 7, "window", 0, k)["kind"]
                     for k in range(200)]


class FakeClient:
    """ServiceClient's interface; every job takes `job_s` of fake time."""

    def __init__(self, clock, job_s, fail_seeds=()):
        self.clock, self.job_s, self.fail = clock, job_s, set(fail_seeds)
        self.n = 0
        self.closed = False

    def submit(self, spec):
        self.n += 1
        self.spec = spec
        return {"job_id": f"job-{self.n}"}

    def wait(self, job_id, timeout_s, poll_s):
        self.clock.t += self.job_s
        state = "failed" if self.spec["seed"] in self.fail else "done"
        return {"state": state, "error": "planted" if state == "failed"
                else None, "wait_s": 0.0, "run_s": self.job_s}

    def result(self, job_id):
        return {"public_input": []}, b"proof-of-" + job_id.encode()

    def close(self):
        self.closed = True


def test_closed_loop_one_client_on_a_fake_clock():
    clock = Clock()
    client = FakeClient(clock, job_s=3.0)
    win = W.Window(clock, seconds=10.0)
    W.run_closed_loop(win, lambda: client, [({"kind": "toy"}, 1)], seed=5,
                      salt="window", clients=1, failed_backoff_s=0)
    # jobs end at 3, 6, 9, 12: the fourth, sent at 9 s, closes it at 12 s
    assert len(win.requests) == 4 and client.closed
    s = W.summarize(win)
    assert s["window_s"] == 12.0 and s["attempted"] == 4
    assert s["proofs_per_s"] == pytest.approx(4 / 12.0)
    assert [r.spec["seed"] for r in win.requests] == [
        W.job_seed(5, "window", 0, k) for k in range(4)]
    warm = W.Window(clock, jobs_per_client=2)
    W.run_closed_loop(warm, lambda: FakeClient(clock, 1.0),
                      [({"kind": "toy"}, 1)], seed=5, salt="warmup",
                      clients=1)
    assert len(warm.requests) == 2
    assert all(r.state == "done" for r in warm.requests)


def test_independent_clients_are_all_waited_for():
    """Four clients, each with its own job outstanding, on one fake clock
    that every finished job moves on by 2.5 s (the jobs add up, as on one
    device). Nobody submits once 15 s have passed; the window closes when
    the last of the jobs sent before that is in, nothing in flight."""
    import threading
    clock = Clock()
    lock = threading.Lock()

    class SharedClient(FakeClient):
        def wait(self, job_id, timeout_s, poll_s):
            with lock:
                return super().wait(job_id, timeout_s, poll_s)

    win = W.Window(clock, seconds=15.0)
    W.run_closed_loop(win, lambda: SharedClient(clock, job_s=2.5),
                      [({"kind": "toy"}, 1)], seed=9, salt="window",
                      clients=4, failed_backoff_s=0)
    s = W.summarize(win)
    sent = [r.t_submit - win.t_open for r in win.requests]
    assert all(t < 15.0 for t in sent) and max(sent) >= 10.0
    assert all(r.t_result is not None for r in win.requests)
    assert s["attempted"] == len(win.requests) == len(win.counted())
    assert win.t_close == max(r.t_result for r in win.requests)
    assert s["window_s"] == 2.5 * len(win.requests)
    assert s["proofs_per_s"] == pytest.approx(1 / 2.5)
    # every client kept its one job outstanding: job k + 1 follows job k
    for c in range(4):
        mine = sorted((r for r in win.requests if r.client == c),
                      key=lambda r: r.k)
        assert [r.k for r in mine] == list(range(len(mine)))
        assert all(a.t_result <= b.t_submit for a, b in zip(mine, mine[1:]))
