"""The comparison that decides `correct`.

Every answer of the window is held to the plain reference
(`benchmark/plain`, through `served.py`): the request was answered, the
proof has the configuration's length, the public input the server reported
is the one the reference works out from the spec, and the reference's
verifier accepts the proof under the key it derives itself. A sample drawn
from the seed (the first job of each sampled client; the configuration says
how many, and a cell of several clients samples them all) is compared byte
for byte with the host oracle's prove of the same spec
(`benchmark/reference`). Every comparison is exact, so every limit is 0; the
lower limits say that the sample was in fact compared and that something
was answered.
"""

import random
import sys


def sample_clients(seed, clients, oracle_jobs):
    """Which clients' first jobs are byte-compared: drawn from the seed."""
    rng = random.Random(f"{seed}/oracle-sample")
    return sorted(rng.sample(range(clients), min(oracle_jobs, clients)))


def byte_diffs(a, b):
    """How many bytes differ between two answers, a missing byte counting."""
    return sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))


def decide(counted, verdicts, oracle_pairs, proof_bytes, oracle_jobs):
    """counted: the window's requests; verdicts: {id(request): check_served
    result} for those that returned a proof; oracle_pairs: [(served bytes or
    None, oracle bytes)] of the sample. Returns (correct, checks) where
    checks maps a short name to {"value", "limit", "holds"}."""
    answered = [r for r in counted if r.state == "done" and r.proof is not None]
    v = [verdicts[id(r)] for r in answered]
    compared = [(s, o) for s, o in oracle_pairs if s is not None]
    numbers = [
        ("unanswered", len(counted) - len(answered), 0, "max"),
        ("wrong_length", sum(len(r.proof) != proof_bytes for r in answered),
         0, "max"),
        ("pub_mismatches", sum(not x["pub_equal"] for x in v), 0, "max"),
        ("verify_failures", sum(not x["verified"] for x in v), 0, "max"),
        ("oracle_byte_diffs", sum(byte_diffs(s, o) for s, o in compared),
         0, "max"),
        ("oracle_compared", len(compared), oracle_jobs, "min"),
        ("answered", len(answered), 1, "min"),
    ]
    checks = {}
    for name, value, limit, rule in numbers:
        holds = value <= limit if rule == "max" else value >= limit
        checks[name] = {"value": value, "limit": limit, "rule": rule,
                        "holds": holds}
    return all(c["holds"] for c in checks.values()), checks


def report(checks, correct, stream=None):
    """Each number compared beside its limit, as the last lines on stderr."""
    stream = stream or sys.stderr
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['rule']} {c['limit']})"
              f" {'ok' if c['holds'] else 'FAILS'}", file=stream)
    print(f"correct: {str(bool(correct)).lower()}", file=stream, flush=True)
