"""Keccak-f[1600] (FIPS 202), the part of STROBE-128 that merlin uses, and
merlin's transcript framing, from their specifications. The tests hold the
permutation to hashlib's SHA3-256 and the transcript to merlin's published
test vector."""

_M64 = (1 << 64) - 1


def _round_constants():
    """The 24 iota constants from the LFSR of FIPS 202, section 3.2.5."""
    out, lfsr = [], 1
    for _ in range(24):
        rc = 0
        for j in range(7):
            if lfsr & 1:
                rc |= 1 << ((1 << j) - 1)
            lfsr = ((lfsr << 1) ^ (0x171 if lfsr & 0x80 else 0)) & 0x1FF
        out.append(rc)
    return out


def _rotations():
    """rho's offsets by the (x, y) walk of section 3.2.2, as r[x][y]."""
    r = [[0] * 5 for _ in range(5)]
    x, y = 1, 0
    for t in range(24):
        r[x][y] = ((t + 1) * (t + 2) // 2) % 64
        x, y = y, (2 * x + 3 * y) % 5
    return r


_RC, _ROT = _round_constants(), _rotations()


def _rotl(v, n):
    return ((v << n) | (v >> (64 - n))) & _M64 if n else v


def keccak_f(state):
    """The permutation on 200 bytes."""
    a = [[int.from_bytes(state[8 * (x + 5 * y):8 * (x + 5 * y) + 8], "little")
          for y in range(5)] for x in range(5)]
    for rc in _RC:
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4] for x in range(5)]
        for x in range(5):
            d = c[x - 1] ^ _rotl(c[(x + 1) % 5], 1)
            for y in range(5):
                a[x][y] ^= d
        b = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rotl(a[x][y], _ROT[x][y])
        for x in range(5):
            for y in range(5):
                a[x][y] = b[x][y] ^ (~b[(x + 1) % 5][y] & b[(x + 2) % 5][y])
        a[0][0] ^= rc
    out = bytearray(200)
    for x in range(5):
        for y in range(5):
            out[8 * (x + 5 * y):8 * (x + 5 * y) + 8] = \
                (a[x][y] & _M64).to_bytes(8, "little")
    return out


RATE = 166                                  # STROBE-128 over keccak-f[1600]
_I, _A, _C, _M = 1, 2, 4, 16                # STROBE's operation flags


class Strobe:
    def __init__(self, protocol):
        st = bytearray(200)
        st[:18] = bytes([1, RATE + 2, 1, 0, 1, 96]) + b"STROBEv1.0.2"
        self.st, self.pos, self.begin = keccak_f(st), 0, 0
        self.operate(_M | _A, protocol)

    def _permute(self):
        self.st[self.pos] ^= self.begin
        self.st[self.pos + 1] ^= 0x04
        self.st[RATE + 1] ^= 0x80
        self.st, self.pos, self.begin = keccak_f(self.st), 0, 0

    def _absorb(self, data):
        for byte in data:
            self.st[self.pos] ^= byte
            self.pos += 1
            if self.pos == RATE:
                self._permute()

    def operate(self, flags, data=b"", more=False, squeeze=0):
        """AD and meta-AD absorb `data`; PRF returns `squeeze` bytes."""
        if not more:
            old, self.begin = self.begin, self.pos + 1
            self._absorb(bytes([old, flags]))
            if flags & _C and self.pos:
                self._permute()
        self._absorb(data)
        out = bytearray()
        for _ in range(squeeze):
            out.append(self.st[self.pos])
            self.st[self.pos] = 0
            self.pos += 1
            if self.pos == RATE:
                self._permute()
        return bytes(out)


class Transcript:
    """merlin 3.0: `Transcript::new`, `append_message`, `challenge_bytes`."""

    def __init__(self, label):
        self.strobe = Strobe(b"Merlin v1.0")
        self.append(b"dom-sep", label)

    def append(self, label, message):
        self.strobe.operate(_M | _A, label)
        self.strobe.operate(_M | _A, len(message).to_bytes(4, "little"), True)
        self.strobe.operate(_A, message)

    def challenge(self, label, count):
        self.strobe.operate(_M | _A, label)
        self.strobe.operate(_M | _A, count.to_bytes(4, "little"), True)
        return self.strobe.operate(_I | _A | _C, squeeze=count)
