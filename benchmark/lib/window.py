"""The measured window and the closed-loop load that fills it.

The window opens at the first SUBMIT after warm-up. Clients send no new
SUBMIT once `seconds` have passed, and the window closes when the RESULT of
every request sent before that is in: at the first RESULT at or after
`seconds` where one client has one job outstanding, after the last of
several where there are more. So nothing is in flight at the close; every
request sent counts, whole, and so does all the time it took. Rates divide
by the window's measured length, so a window of six ten-second proofs does
not move in steps of a sixth. Clock and clients are injected, so the
arithmetic is tested on a fake clock with no service.
"""

import random
import threading
import time


def job_seed(seed, salt, client, k):
    """The seed of client `client`'s k-th job in a run of `--seed seed`:
    the same run seed gives the same jobs, in any order of arrival."""
    return random.Random(f"{seed}/{salt}/{client}/{k}").randrange(1, 1 << 31)


def draw_spec(job_mix, seed, salt, client, k):
    """The k-th job spec of a client: a configuration's job drawn by weight
    (one configuration: always that one), with its own seed."""
    rng = random.Random(f"{seed}/{salt}/mix/{client}/{k}")
    jobs = [j for j, _w in job_mix]
    job = rng.choices(jobs, weights=[w for _j, w in job_mix])[0]
    return dict(job, seed=job_seed(seed, salt, client, k))


class Request:
    __slots__ = ("client", "k", "spec", "job_id", "t_submit", "t_result",
                 "state", "status", "header", "proof", "error")

    def __init__(self, client, k, spec):
        self.client, self.k, self.spec = client, k, spec
        self.job_id = self.t_submit = self.t_result = None
        self.state = self.status = self.header = self.proof = self.error = None

    @property
    def latency_s(self):
        return self.t_result - self.t_submit


class Window:
    """Shared by the client threads of one closed loop. Either `seconds` is
    set (the measured window: no SUBMIT after it, closed by the last
    RESULT) or, for warm-up, `jobs_per_client`."""

    def __init__(self, clock, seconds=None, jobs_per_client=None):
        self.clock = clock
        self.seconds = seconds
        self.jobs_per_client = jobs_per_client
        self.lock = threading.Lock()
        self.t_open = None
        self.t_close = None
        self.requests = []

    def submitting(self, req):
        """Called just before a SUBMIT is sent; the first one opens."""
        with self.lock:
            now = self.clock()
            if self.t_open is None:
                self.t_open = now
            req.t_submit = now
            self.requests.append(req)

    def arrived(self, req):
        """Called when a request's RESULT bytes are in (or it has failed)."""
        with self.lock:
            req.t_result = self.clock()

    def wants_more(self, k):
        """Whether a client that has finished k jobs sends another."""
        with self.lock:
            if self.jobs_per_client is not None:
                return k < self.jobs_per_client
            return (self.t_open is None
                    or self.clock() - self.t_open < self.seconds)

    def close(self):
        """Called when every client has stopped: the last RESULT closes."""
        done = [r.t_result for r in self.requests if r.t_result is not None]
        self.t_close = max(done) if done else self.t_open

    def counted(self):
        """Every request of the window: each was sent inside it and waited
        for, so none is in flight when it closes."""
        return [r for r in self.requests if r.t_result is not None]

    def length_s(self):
        return self.t_close - self.t_open if self.requests else 0.0


def summarize(window):
    """The window's end-to-end arithmetic: what was attempted, what failed,
    the rate over the measured length and the latencies of all requests
    that finished inside."""
    inside = window.counted()
    good = [r for r in inside if r.state == "done" and r.proof is not None]
    length = window.length_s()
    lat = [r.latency_s for r in good]
    return {
        "attempted": len(inside),
        "failed": len(inside) - len(good),
        "sent_before_s": window.seconds,
        "window_s": length,
        "proofs_per_s": len(good) / length if length > 0 and good else None,
        "latency_mean_s": sum(lat) / len(lat) if lat else None,
        "latency_max_s": max(lat) if lat else None,
    }


def run_closed_loop(window, make_client, job_mix, seed, salt, clients,
                    wait_timeout_s=300.0, poll_s=0.02, failed_backoff_s=0.25):
    """`clients` threads, each with one job outstanding: SUBMIT, wait for
    the RESULT, record, and again while the window wants more; when every
    client has stopped the window is closed, with the service idle.
    `make_client()` returns an object with submit / wait / result / close
    (ServiceClient's interface). Raises the first client thread's error."""
    errors = []

    def one_client(c):
        client = make_client()
        try:
            k = 0
            while window.wants_more(k):
                req = Request(c, k, draw_spec(job_mix, seed, salt, c, k))
                window.submitting(req)
                try:
                    req.job_id = client.submit(req.spec)["job_id"]
                    req.status = client.wait(req.job_id,
                                             timeout_s=wait_timeout_s,
                                             poll_s=poll_s)
                    req.state = req.status["state"]
                    if req.state == "done":
                        req.header, req.proof = client.result(req.job_id)
                    else:
                        req.error = req.status.get("error")
                except Exception as e:  # noqa: BLE001 - a request that
                    # raises is a failed request; the loop goes on
                    req.state, req.error = "error", repr(e)
                window.arrived(req)
                k += 1
                if req.state != "done":
                    time.sleep(failed_backoff_s)  # no hot loop of failures
        except BaseException as e:  # noqa: BLE001 - re-raised by the caller
            errors.append(e)
        finally:
            client.close()

    threads = [threading.Thread(target=one_client, args=(c,),
                                name=f"bench-client-{c}", daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    window.close()
    if errors:
        raise errors[0]
    return window
