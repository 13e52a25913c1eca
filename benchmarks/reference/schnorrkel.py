"""The plain reference for sr25519 (schnorrkel) signatures, and the
light verification of a commit whose validators hold keys of two types.

Plain Python integers and the standard library only; nothing of the
program under test is imported. Written from the published
descriptions, bottom up:

  keccak-f[1600]        FIPS 202, the permutation alone
  STROBE-128            strobe.sourceforge.io, v1.0.2: the three
                        operations merlin uses (meta-AD, AD, PRF)
  merlin transcript     merlin.cool: `Merlin v1.0`, `dom-sep`, a
                        message as label, little-endian length, bytes
  ristretto255          RFC 9496 section 4.3.1 (decode), 4.3.2
                        (encode), 4.3.3 (equality), over edwards25519
                        in extended coordinates
  schnorrkel verify     SigningContext with the EMPTY context (what
                        upstream's crypto/sr25519/privkey.go signs
                        under): `sign-bytes`, `proto-name` =
                        `Schnorr-sig`, `sign:pk`, `sign:R`, then 64
                        bytes of `sign:c` reduced mod L; accept iff
                        s B - k A == R as ristretto elements

A signature is 64 bytes, R then s, with schnorrkel's marker (bit 7 of
the last byte) set; it is rejected without the marker, with s >= L
once the marker is cleared, or where the key or R is no canonical
ristretto encoding (not below p, odd, or off the group).

Pinned by tier-1 (`tests/test_schnorrkel_reference.py`) to merlin's
published transcript vector and to RFC 9496 appendix A.1's multiples of
the generator; it also agrees there with the program's own host
implementation on seeded honest and damaged rows.

`verify_commit_light` applies `reference/plain.py`'s order, 2/3 rule
and blame to a commit of mixed key types: each row goes to its own
type's check, OpenSSL for ed25519 as in every other cell.
"""
from __future__ import annotations

import struct
from functools import lru_cache
from typing import List, Optional, Sequence, Tuple

P = 2 ** 255 - 19
L = 2 ** 252 + 27742317777372353535851937790883648493
D = -121665 * pow(121666, P - 2, P) % P
D2 = 2 * D % P
SQRT_M1 = pow(2, (P - 1) // 4, P)

# --------------------------------------------------------------------------
# keccak-f[1600]
# --------------------------------------------------------------------------

_M64 = (1 << 64) - 1


def _round_constants() -> List[int]:
    out, r = [], 1
    for _ in range(24):
        rc = 0
        for j in range(7):
            if r & 1:
                rc |= 1 << ((1 << j) - 1)
            r = ((r << 1) ^ (0x71 if r & 0x80 else 0)) & 0xFF
        out.append(rc)
    return out


def _rotations() -> List[int]:
    rot = [0] * 25
    x, y = 1, 0
    for t in range(24):
        rot[x + 5 * y] = ((t + 1) * (t + 2) // 2) % 64
        x, y = y, (2 * x + 3 * y) % 5
    return rot


_RC = _round_constants()
_ROT = _rotations()
# pi: lane (x, y) moves to (y, 2x + 3y)
_PI = [(x + 5 * y, y + 5 * ((2 * x + 3 * y) % 5), _ROT[x + 5 * y])
       for y in range(5) for x in range(5)]


def keccak_f1600(a: List[int]) -> List[int]:
    """The permutation on 25 lanes of 64 bits, lane (x, y) at x + 5y."""
    for rc in _RC:
        c = [a[x] ^ a[x + 5] ^ a[x + 10] ^ a[x + 15] ^ a[x + 20]
             for x in range(5)]
        d = [c[(x - 1) % 5]
             ^ (((c[(x + 1) % 5] << 1) | (c[(x + 1) % 5] >> 63)) & _M64)
             for x in range(5)]
        b = [0] * 25
        for src, dst, r in _PI:
            v = a[src] ^ d[src % 5]
            b[dst] = ((v << r) | (v >> (64 - r))) & _M64 if r else v
        a = [b[i] ^ (~b[(i + 1) % 5 + i // 5 * 5]
                     & b[(i + 2) % 5 + i // 5 * 5]) for i in range(25)]
        a[0] ^= rc
    return a


# --------------------------------------------------------------------------
# STROBE-128 (the part merlin uses) and the merlin transcript
# --------------------------------------------------------------------------

_RATE = 166  # 200 - 128 / 4 - 2
_FLAG_I, _FLAG_A, _FLAG_C, _FLAG_M = 1, 2, 4, 16


class Strobe128:
    def __init__(self, protocol: bytes):
        self.st = bytearray(200)
        self.st[0:6] = bytes([1, _RATE + 2, 1, 0, 1, 96])
        self.st[6:18] = b"STROBEv1.0.2"
        self._permute()
        self.pos = self.pos_begin = self.cur_flags = 0
        self.meta_ad(protocol, False)

    def clone(self) -> "Strobe128":
        other = object.__new__(Strobe128)
        other.st = bytearray(self.st)
        other.pos, other.pos_begin = self.pos, self.pos_begin
        other.cur_flags = self.cur_flags
        return other

    def _permute(self) -> None:
        self.st[:] = struct.pack(
            "<25Q", *keccak_f1600(list(struct.unpack("<25Q", self.st))))

    def _run_f(self) -> None:
        self.st[self.pos] ^= self.pos_begin
        self.st[self.pos + 1] ^= 0x04
        self.st[_RATE + 1] ^= 0x80
        self._permute()
        self.pos = self.pos_begin = 0

    def _absorb(self, data: bytes) -> None:
        for byte in data:
            self.st[self.pos] ^= byte
            self.pos += 1
            if self.pos == _RATE:
                self._run_f()

    def _squeeze(self, n: int) -> bytes:
        out = bytearray()
        for _ in range(n):
            out.append(self.st[self.pos])
            self.st[self.pos] = 0
            self.pos += 1
            if self.pos == _RATE:
                self._run_f()
        return bytes(out)

    def _begin_op(self, flags: int, more: bool) -> None:
        if more:
            if flags != self.cur_flags:
                raise ValueError("continued a different operation")
            return
        old_begin = self.pos_begin
        self.pos_begin = self.pos + 1
        self.cur_flags = flags
        self._absorb(bytes([old_begin, flags]))
        if flags & _FLAG_C and self.pos != 0:
            self._run_f()

    def meta_ad(self, data: bytes, more: bool) -> None:
        self._begin_op(_FLAG_M | _FLAG_A, more)
        self._absorb(data)

    def ad(self, data: bytes, more: bool) -> None:
        self._begin_op(_FLAG_A, more)
        self._absorb(data)

    def prf(self, n: int) -> bytes:
        self._begin_op(_FLAG_I | _FLAG_A | _FLAG_C, False)
        return self._squeeze(n)


class Transcript:
    def __init__(self, label: bytes):
        self.strobe = Strobe128(b"Merlin v1.0")
        self.append_message(b"dom-sep", label)

    def clone(self) -> "Transcript":
        other = object.__new__(Transcript)
        other.strobe = self.strobe.clone()
        return other

    def append_message(self, label: bytes, message: bytes) -> None:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(struct.pack("<I", len(message)), True)
        self.strobe.ad(message, False)

    def challenge_bytes(self, label: bytes, n: int) -> bytes:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(struct.pack("<I", n), True)
        return self.strobe.prf(n)


@lru_cache(maxsize=1)
def _signing_context() -> Transcript:
    """schnorrkel's SigningContext::new(b""): the state every
    signature's transcript starts from."""
    t = Transcript(b"SigningContext")
    t.append_message(b"", b"")
    return t


def challenge(msg: bytes, pub: bytes, r_enc: bytes) -> int:
    """The scalar k of one signature: 64 bytes of `sign:c`, mod L."""
    t = _signing_context().clone()
    t.append_message(b"sign-bytes", msg)
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pub)
    t.append_message(b"sign:R", r_enc)
    return int.from_bytes(t.challenge_bytes(b"sign:c", 64), "little") % L


# --------------------------------------------------------------------------
# edwards25519 in extended coordinates (X : Y : Z : T), x y = T / Z
# --------------------------------------------------------------------------

Point = Tuple[int, int, int, int]
IDENTITY: Point = (0, 1, 1, 0)


def _add(p: Point, q: Point) -> Point:
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = t1 * D2 % P * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def _double(p: Point) -> Point:
    x1, y1, z1, _ = p
    xx, yy = x1 * x1 % P, y1 * y1 % P
    zz2 = 2 * z1 * z1 % P
    yp, ym = yy + xx, yy - xx
    xc = (x1 + y1) * (x1 + y1) - yp
    t = zz2 - ym
    return (xc * t % P, yp * ym % P, ym * t % P, xc * yp % P)


def _neg(p: Point) -> Point:
    return (-p[0] % P, p[1], p[2], -p[3] % P)


def _base_point() -> Point:
    y = 4 * pow(5, P - 2, P) % P
    x2 = (y * y - 1) * pow(D * y * y + 1, P - 2, P) % P
    x = pow(x2, (P + 3) // 8, P)
    if (x * x - x2) % P:
        x = x * SQRT_M1 % P
    if x & 1:
        x = P - x
    return (x, y, 1, x * y % P)


BASE = _base_point()


@lru_cache(maxsize=1)
def _base_table() -> List[List[Point]]:
    """[w][d] = d * 16**w * B, for the 64 nibbles of a scalar."""
    table, pt = [], BASE
    for _ in range(64):
        row = [IDENTITY]
        for _ in range(15):
            row.append(_add(row[-1], pt))
        table.append(row)
        pt = _add(row[-1], pt)
    return table


def base_mul(k: int) -> Point:
    """k B by the fixed-base table: 64 additions."""
    k %= L
    acc = IDENTITY
    for row in _base_table():
        if k & 15:
            acc = _add(acc, row[k & 15])
        k >>= 4
    return acc


def mul(k: int, p: Point) -> Point:
    """k P by 4-bit windows: 252 doublings, up to 64 additions."""
    k %= L
    row = [IDENTITY, p]
    for _ in range(14):
        row.append(_add(row[-1], p))
    acc = IDENTITY
    for shift in range(252, -1, -4):
        if shift != 252:
            acc = _double(_double(_double(_double(acc))))
        digit = (k >> shift) & 15
        if digit:
            acc = _add(acc, row[digit])
    return acc


# --------------------------------------------------------------------------
# ristretto255 (RFC 9496 section 4.3)
# --------------------------------------------------------------------------


def _sqrt_ratio_m1(u: int, v: int) -> Tuple[bool, int]:
    v3 = v * v % P * v % P
    v7 = v3 * v3 % P * v % P
    r = u * v3 % P * pow(u * v7 % P, (P - 5) // 8, P) % P
    check = v * r % P * r % P
    correct = check == u % P
    flipped = check == -u % P
    flipped_i = check == -u * SQRT_M1 % P
    if flipped or flipped_i:
        r = r * SQRT_M1 % P
    if r & 1:
        r = P - r
    return correct or flipped, r


INVSQRT_A_MINUS_D = _sqrt_ratio_m1(1, (-1 - D) % P)[1]


def decode(enc: bytes) -> Optional[Point]:
    """The element a 32-byte string encodes, or None where it is no
    canonical encoding (section 4.3.1)."""
    if len(enc) != 32:
        return None
    s = int.from_bytes(enc, "little")
    if s >= P or s & 1:
        return None
    ss = s * s % P
    u1, u2 = (1 - ss) % P, (1 + ss) % P
    u2_sqr = u2 * u2 % P
    v = (-(D * u1 % P * u1) - u2_sqr) % P
    was_square, invsqrt = _sqrt_ratio_m1(1, v * u2_sqr % P)
    den_x = invsqrt * u2 % P
    den_y = invsqrt * den_x % P * v % P
    x = 2 * s * den_x % P
    if x & 1:
        x = P - x
    y = u1 * den_y % P
    t = x * y % P
    if not was_square or t & 1 or y == 0:
        return None
    return (x, y, 1, t)


def encode(p: Point) -> bytes:
    """Section 4.3.2."""
    x0, y0, z0, t0 = p
    u1 = (z0 + y0) * (z0 - y0) % P
    u2 = x0 * y0 % P
    _, invsqrt = _sqrt_ratio_m1(1, u1 * u2 % P * u2 % P)
    den1, den2 = invsqrt * u1 % P, invsqrt * u2 % P
    z_inv = den1 * den2 % P * t0 % P
    ix0, iy0 = x0 * SQRT_M1 % P, y0 * SQRT_M1 % P
    enchanted = den1 * INVSQRT_A_MINUS_D % P
    if t0 * z_inv % P & 1:
        x, y, den_inv = iy0, ix0, enchanted
    else:
        x, y, den_inv = x0, y0, den2
    if x * z_inv % P & 1:
        y = -y % P
    s = den_inv * (z0 - y) % P
    if s & 1:
        s = P - s
    return s.to_bytes(32, "little")


def equal(p: Point, q: Point) -> bool:
    """Section 4.3.3: equality of the ristretto elements."""
    return ((p[0] * q[1] - p[1] * q[0]) % P == 0
            or (p[1] * q[1] - p[0] * q[0]) % P == 0)


# --------------------------------------------------------------------------
# schnorrkel
# --------------------------------------------------------------------------


@lru_cache(maxsize=16384)
def _key_point(pub: bytes) -> Optional[Point]:
    """A validator's key signs every commit: decoded once."""
    return decode(pub)


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if len(pub) != 32 or len(sig) != 64 or not sig[63] & 0x80:
        return False
    s = int.from_bytes(sig[32:], "little") & ((1 << 255) - 1)
    if s >= L:
        return False
    a, r = _key_point(bytes(pub)), decode(sig[:32])
    if a is None or r is None:
        return False
    k = challenge(bytes(msg), bytes(pub), sig[:32])
    return equal(_add(base_mul(s), _neg(mul(k, a))), r)


# --------------------------------------------------------------------------
# a commit of mixed key types
# --------------------------------------------------------------------------


def verify_commit_light(pubs: Sequence[bytes], key_types: Sequence[str],
                        powers: Sequence[int], msgs: Sequence[bytes],
                        sigs: Sequence[Optional[bytes]]) -> Tuple:
    """`plain.verify_commit_light` over validators of two key types:
    the same prefix (in validator order, until MORE than 2/3 of the
    power has signed), the same blame (the first bad signature
    examined, by its index in the commit), each row checked by its own
    type's verifier."""
    from reference import plain

    check = {"ed25519": plain.verify_sig, "sr25519": verify}
    needed = plain.needed_power(powers)
    examined: List[int] = []
    tallied = 0
    for i, sig in enumerate(sigs):
        if sig is None:
            continue
        examined.append(i)
        tallied += powers[i]
        if tallied > needed:
            break
    if tallied <= needed:
        return ("not_enough_power", needed)
    for i in examined:
        if not check[key_types[i]](pubs[i], msgs[i], sigs[i]):
            return ("invalid_signature", i)
    return plain.OK
