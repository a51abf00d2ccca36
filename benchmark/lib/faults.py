"""Faults planted in the program's timed path, for the control and the tests.

None of this runs in a benchmark run. `benchmark/control.py` (on the chip,
at a cell's own size) and `tests/benchmark` (on the CPU, at a toy size) plant
one fault at a time underneath a window and see `correct` come out false.
Each fault is a patch of a name the pool worker looks up at call time, undone
on exit; the program's files are not touched.
"""

import contextlib
import random

from ..reference.oracle import REUSED_BLINDING_SEED


class _RandomShim:
    """Stands in for the `random` module inside service/pool.py."""

    def __init__(self, random_cls):
        self.Random = random_cls

    def __getattr__(self, name):
        return getattr(random, name)


def _flip_middle_byte(serialize):
    def altered(proof):
        raw = serialize(proof)
        mid = len(raw) // 2
        return raw[:mid] + bytes([raw[mid] ^ 0x01]) + raw[mid + 1:]
    return altered


def _refuse(*_args, **_kwargs):
    raise RuntimeError("planted fault: the prover returns no answer")


FAULTS = ("answer_altered", "blinding_reused", "answer_missing")


@contextlib.contextmanager
def planted(name):
    """answer_altered: one bit of every proof flipped where it is
    serialized (the verifier must reject it). blinding_reused: every proof
    blinded from one fixed seed, not from its job's (zero blinders trip the
    prover's own degree assertion, so that is the nearest it will run); the
    proof still verifies and only the byte comparison with the oracle sees it.
    answer_missing: every prove raises, so requests fail."""
    from distributed_plonk_tpu.service import pool
    if name == "answer_altered":
        patches = {"serialize_proof": _flip_middle_byte(pool.serialize_proof)}
    elif name == "blinding_reused":
        patches = {"random": _RandomShim(
            lambda _seed=None: random.Random(REUSED_BLINDING_SEED))}
    elif name == "answer_missing":
        patches = {"prove": _refuse, "prove_many": _refuse,
                   "prove_pipelined": _refuse}
    else:
        raise ValueError(f"no fault {name!r}; known: {FAULTS}")
    saved = {k: getattr(pool, k) for k in patches}
    try:
        for k, v in patches.items():
            setattr(pool, k, v)
        yield
    finally:
        for k, v in saved.items():
            setattr(pool, k, v)
