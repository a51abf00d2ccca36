"""What the frozen prover imports of the program's trace module, and no more:
the shape-only work model its spans are tagged with, and the tracer that
records nothing. The program's file (spans, sockets, merging, export) is not
copied."""

from contextlib import contextmanager


# --- workload flops/bytes models ---------------------------------------------
# The bench.py attribution model, exported so prover/worker kernel spans
# can carry `flops`/`data_bytes` attrs and the metrics layer can expose
# live per-stage MFU instead of bench-only numbers. "Useful flops" = the
# band FMAs of the field muls each kernel performs (limb-matrix SOS
# multiplication: 3 byte-product bands of (2L)^2 MACs, 2 flops each).

FR_BAND_FLOPS = 3 * 32 * 32 * 2      # one Fr mul (L=16 u16 limbs)
FQ_BAND_FLOPS = 3 * 48 * 48 * 2      # one Fq mul (L=24)
FR_BYTES = 32
MSM_MULS_PER_POINT = 32 * 11         # signed radix-256: 32 windows, ~11
                                     # Fq muls per mixed add


def ntt_flops(n, count=1):
    """Model flops for `count` n-point NTTs."""
    if n < 2:
        return 0
    return count * (n // 2) * (n.bit_length() - 1) * FR_BAND_FLOPS


def msm_flops(n_points, count=1):
    """Model flops for `count` n-point G1 MSMs."""
    return count * n_points * MSM_MULS_PER_POINT * FQ_BAND_FLOPS


class _NullTracer:
    """No-op tracer: `span` costs one contextmanager enter/exit."""

    events = ()
    trace_id = None

    @contextmanager
    def span(self, name, **attrs):
        yield None

    def add_event(self, name, ts, dur_s, parent=None, **attrs):
        return None

    def context(self):
        return None

    def totals(self, depth=1):
        return {}

    def dump(self):
        return {}

    def to_json(self):
        return "{}"


NULL_TRACER = _NullTracer()
