"""Work one proof needs, from the configuration's sizes alone.

The counts are the protocol's (TurboPlonk as the reference's dispatcher runs
it), not the program's: whatever window width, chunking or kernel the
program picks, a proof at domain n commits 13 polynomials and transforms 33,
so a roofline share built on these counts moves only when device time does.

Flops are the shape-only model the program's tracer already carried
(`distributed_plonk_tpu/trace.py` `ntt_flops` / `msm_flops`, copied here):
"useful flops" are the band multiply-adds of the field multiplications, a
limb-matrix product of 3 byte-bands of (2L)^2 MACs at 2 flops each. Bytes
count every point and scalar of an MSM read once, and every NTT read once
and written once.
"""

NUM_WIRE_TYPES = 5
NUM_SELECTORS = 13

FR_BAND_FLOPS = 3 * 32 * 32 * 2      # one Fr mul (16 u16 limbs)
FQ_BAND_FLOPS = 3 * 48 * 48 * 2      # one Fq mul (24 u16 limbs)
MSM_MULS_PER_POINT = 32 * 11         # signed radix-256: 32 windows, about
                                     # 11 Fq muls per mixed add
FR_BYTES = 32
G1_AFFINE_BYTES = 2 * 48


def ntt_flops(n, count=1):
    """Model flops for `count` n-point NTTs."""
    if n < 2:
        return 0
    return count * (n // 2) * (n.bit_length() - 1) * FR_BAND_FLOPS


def msm_flops(n_points, count=1):
    """Model flops for `count` n-point G1 MSMs."""
    return count * n_points * MSM_MULS_PER_POINT * FQ_BAND_FLOPS


def msm_shapes(n):
    """[(points, count)] of one proof's commitments at domain n: five wire
    polynomials and five quotient parts of n + 2 coefficients, the
    permutation product of n + 3, two opening witnesses of n + 2."""
    nw = NUM_WIRE_TYPES
    return [(n + 2, nw), (n + 3, 1), (n + 2, nw), (n + 2, 2)]


def ntt_shapes(n, quot_domain):
    """[(size, count)] of one proof's transforms: at n the wires, the
    permutation product and the public input; at the quotient domain one
    coset FFT for each selector, sigma and wire, the product and the public
    input, and the quotient's one coset iFFT."""
    nw = NUM_WIRE_TYPES
    return [(n, nw + 2), (quot_domain, NUM_SELECTORS + 2 * nw + 2 + 1)]


def family_work(family, sizes):
    """{"flops", "bytes"} one proof needs of the `msm` or `ntt` family, or
    of both together (`prove`), at the configuration's `sizes`
    ({"domain_size", "quotient_domain_size"})."""
    n = int(sizes["domain_size"])
    if family == "prove":
        parts = [family_work(f, sizes) for f in ("msm", "ntt")]
        return {k: sum(p[k] for p in parts) for k in ("flops", "bytes")}
    if family == "msm":
        shapes = msm_shapes(n)
        flops = sum(msm_flops(p, c) for p, c in shapes)
        nbytes = sum(c * p * (G1_AFFINE_BYTES + FR_BYTES) for p, c in shapes)
    elif family == "ntt":
        shapes = ntt_shapes(n, int(sizes["quotient_domain_size"]))
        flops = sum(ntt_flops(s, c) for s, c in shapes)
        nbytes = sum(c * s * 2 * FR_BYTES for s, c in shapes)
    else:
        raise ValueError(f"no work model for family {family!r}")
    return {"flops": flops, "bytes": nbytes}


def least_seconds(work, peaks):
    """(seconds, which) the chip needs at its peaks: the larger of flops
    over peak flops and bytes over peak bandwidth, and which one bounds."""
    by_flops = work["flops"] / peaks["flops_per_s"]
    by_bytes = work["bytes"] / peaks["bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
