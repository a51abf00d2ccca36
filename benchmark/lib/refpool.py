"""Jax-free worker processes that run the plain reference.

The process that holds the chip only hands specs and served bytes to these
workers and reads verdicts back; the reference's arithmetic (a host prove
of a minute or two, a second of curve arithmetic per verify) never shares
an interpreter with the service under test. `workers=0` runs the calls
inline, for tests. An oracle prove fans its pieces out over a pool of
`fanout` processes of its own (`fanout.py`), started inside the worker
that runs it.
"""

import multiprocessing
import os
import signal
from concurrent.futures import Future, ProcessPoolExecutor


def descendants(pids):
    """Every process below `pids` at this moment, read from /proc."""
    children = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rpartition(")")[2].split()[1])
        except (OSError, IndexError, ValueError):
            continue                    # it ended while the list was read
        children.setdefault(ppid, []).append(int(entry))
    found, todo = [], list(pids)
    while todo:
        kids = children.get(todo.pop(), [])
        found += kids
        todo += kids
    return found


class RefPool:
    def __init__(self, workers, cache_dir, fanout):
        self.cache_dir, self.fanout = cache_dir, fanout
        self._pool = None
        if workers > 0:
            self._pool = ProcessPoolExecutor(
                max_workers=workers,
                mp_context=multiprocessing.get_context("spawn"))

    def _submit(self, fn, *args, **kwargs):
        if self._pool is not None:
            return self._pool.submit(fn, *args, **kwargs)
        fut = Future()
        try:
            fut.set_result(fn(*args, **kwargs))
        except Exception as e:  # noqa: BLE001 - read back through the future
            fut.set_exception(e)
        return fut

    def oracle_proof(self, spec, precision="full"):
        from ..reference import oracle
        return self._submit(oracle.oracle_proof, spec,
                            cache_dir=self.cache_dir, precision=precision,
                            workers=self.fanout)

    def check_served(self, spec, proof, header_pub, tau):
        from . import served
        return self._submit(served.check_served, spec, proof, header_pub, tau)

    def close(self, kill=False):
        """Stop every worker and wait until each has ended. `kill` ends a
        worker in the middle of its task (after an error, when nobody will
        read the answer) where otherwise the task is waited for, and with it
        every process of the fan-out pools below it: the workers are stopped
        first, so that none starts another while the tree is read."""
        if self._pool is None:
            return
        procs = list((self._pool._processes or {}).values())
        if kill:
            for p in procs:
                _signal(p.pid, signal.SIGSTOP)
            for pid in descendants([p.pid for p in procs]):
                _signal(pid, signal.SIGKILL)
            for p in procs:
                _signal(p.pid, signal.SIGKILL)
        self._pool.shutdown(wait=not kill, cancel_futures=True)
        for p in procs:
            p.join(timeout=30)
        self._pool = None


def _signal(pid, sig):
    try:
        os.kill(pid, sig)
    except ProcessLookupError:
        pass
