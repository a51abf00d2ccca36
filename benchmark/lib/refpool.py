"""Jax-free worker processes that run the plain reference.

The process that holds the chip only hands specs and served bytes to these
workers and reads verdicts back; the reference's arithmetic (a host prove
of a minute or two, a second of curve arithmetic per verify) never shares
an interpreter with the service under test. `workers=0` runs the calls
inline, for tests.
"""

import multiprocessing
from concurrent.futures import Future, ProcessPoolExecutor


class RefPool:
    def __init__(self, workers, cache_dir):
        self.cache_dir = cache_dir
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
                            cache_dir=self.cache_dir, precision=precision)

    def check_served(self, spec, proof, header_pub, tau):
        from . import served
        return self._submit(served.check_served, spec, proof, header_pub, tau)

    def close(self, kill=False):
        """Stop every worker and wait until each has ended. `kill` ends a
        worker in the middle of its task (after an error, when nobody will
        read the answer) where otherwise the task is waited for."""
        if self._pool is None:
            return
        procs = list((self._pool._processes or {}).values())
        if kill:
            for p in procs:
                p.terminate()
        self._pool.shutdown(wait=not kill, cancel_futures=True)
        for p in procs:
            p.join(timeout=30)
        self._pool = None
