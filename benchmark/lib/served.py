"""Hold one served answer to the plain reference (in a jax-free worker).

The statement (the job's public input), the verifying key, the transcript
and the verifier are `benchmark/plain`'s, written from the specifications
and sharing no code with the program. One thing is taken from the frozen
copy of the program's circuit builder (`benchmark/reference`): the gate
tables of the job's shape, thirteen selector columns and five columns of
variable ids, which are the deployment's definition of the statement's
constraint system. The key is derived from those tables here, not read
from anything the program or its copy has derived.
"""

from ..plain import statement, verifier

_KEYS = {}


def _tables(spec):
    from ..reference.oracle import build_circuit
    ckt = build_circuit(dict(spec, seed=0))      # the shape, not the job
    return ckt.selectors, ckt.wire_variables, ckt.num_inputs


def key_for(spec, tau):
    shape = tuple(sorted((k, v) for k, v in spec.items() if k != "seed"))
    if (shape, tau) not in _KEYS:
        _KEYS[shape, tau] = verifier.derive_key(*_tables(spec), tau)
    return _KEYS[shape, tau]


def check_served(spec, proof_bytes, header_public_input, tau):
    """{"pub_equal", "verified", "why"}: whether the public input the
    server reported is the statement's, and whether the plain verifier
    accepts the served bytes for the statement's own public input."""
    public = statement.public_input(spec)
    try:
        served_public = [int(x, 16) for x in header_public_input]
    except (TypeError, ValueError):
        served_public = None
    verified, why = verifier.verify(key_for(spec, tau), public,
                                    proof_bytes, tau)
    return {"pub_equal": served_public == public, "verified": verified,
            "why": why}
