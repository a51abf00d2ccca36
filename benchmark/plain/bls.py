"""BLS12-381: the two prime fields, G1 in affine coordinates, and the
zcash/IETF compressed encoding of a G1 point. Plain Python integers, one
modular inversion per addition: slow and short. `None` is the point at
infinity."""

# z, the curve family's parameter; both moduli follow from it
Z = -0xD201000000010000
R = Z ** 4 - Z ** 2 + 1                      # order of G1, the scalar field
P = (Z - 1) ** 2 * R // 3 + Z                # the base field
assert R.bit_length() == 255 and P.bit_length() == 381 and P % 4 == 3

G1 = (0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
      0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1)


def on_curve(pt):
    return pt is None or (pt[1] * pt[1] - pt[0] ** 3 - 4) % P == 0


def neg(pt):
    return None if pt is None else (pt[0], -pt[1] % P)


def add(a, b):
    if a is None:
        return b
    if b is None:
        return a
    (x1, y1), (x2, y2) = a, b
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        slope = 3 * x1 * x1 * pow(2 * y1, -1, P) % P
    else:
        slope = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (slope * slope - x1 - x2) % P
    return x3, (slope * (x1 - x3) - y1) % P


def mul(pt, k):
    """k times pt; k is taken as it is, so `mul(pt, R)` tests the order."""
    acc = None
    while k > 0:
        if k & 1:
            acc = add(acc, pt)
        pt = add(pt, pt)
        k >>= 1
    return acc


def combine(points, scalars):
    """sum of scalars[i] * points[i], scalars taken mod R."""
    acc = None
    for pt, k in zip(points, scalars):
        acc = add(acc, mul(pt, k % R))
    return acc


def in_g1(pt):
    return on_curve(pt) and mul(pt, R) is None


def decode_g1(raw):
    """48 bytes, zcash/IETF compressed (big-endian x; top bits: 0x80
    compressed, 0x40 infinity, 0x20 the larger y) -> point of G1.
    Raises ValueError on anything that is not a canonical encoding of a
    point of the order-R subgroup."""
    raw = bytes(raw)
    if len(raw) != 48 or not raw[0] & 0x80:
        raise ValueError("not a compressed G1 encoding")
    larger = bool(raw[0] & 0x20)
    x = int.from_bytes(raw, "big") & ((1 << 381) - 1)
    if raw[0] & 0x40:
        if larger or x:
            raise ValueError("malformed infinity")
        return None
    if x >= P:
        raise ValueError("x is not reduced")
    y2 = (x ** 3 + 4) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        raise ValueError("x is not on the curve")
    if (y > P - y) != larger:
        y = P - y
    if mul((x, y), R) is not None:
        raise ValueError("point outside the order-R subgroup")
    return x, y


def ark_g1(pt):
    """A point as arkworks 0.3 serialises it compressed, which is what the
    transcript absorbs: 48 bytes little-endian x; top byte bit 7 set when y
    is the larger root, bit 6 for infinity."""
    if pt is None:
        return bytes(47) + b"\x40"
    out = bytearray(pt[0].to_bytes(48, "little"))
    if pt[1] > P - pt[1]:
        out[47] |= 0x80
    return bytes(out)


def fr_bytes(x):
    return (x % R).to_bytes(32, "little")


def root_of_unity(n):
    """The generator of the size-n subgroup of Fr* that arkworks' radix-2
    domain uses: 7 is the field's generator, r - 1 = 2^32 * odd."""
    assert n & (n - 1) == 0 and n <= 1 << 32
    return pow(pow(7, (R - 1) >> 32, R), (1 << 32) // n, R)
