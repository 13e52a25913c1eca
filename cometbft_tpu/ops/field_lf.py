"""Limbs-first (transposed) field arithmetic for Pallas TPU kernels.

Same algorithms as cometbft_tpu.ops.field (13-bit x 20 int32 limbs), but
with the LIMB axis first and the batch in trailing lanes: a field element
batch is (NLIMBS, B). On TPU the last dim maps to the 128-wide lane axis,
so every field op vectorizes perfectly across the signature batch while
limb shifts become cheap sublane moves. The (..., NLIMBS) layout of
field.Field would waste 108/128 lanes inside a kernel.

Mosaic (the Pallas TPU compiler) does not support closed-over array
constants inside kernels ("You should pass them as inputs"), so every
field constant here is kept as a tuple of Python ints and materialized
in-trace with broadcasted_iota + scalar selects (`const_col`). The
compiler folds these into vector constants; nothing is captured.

Kept separate from field.Field on purpose: this module is the in-kernel
(VMEM-resident) dialect used by ops.ed25519_pallas; field.Field remains the
host/XLA dialect. The numeric discipline (mul-safe bound |l| <= 2^13+2^4,
double-carry after wide ops) is identical — see field.py for the bound
derivations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from cometbft_tpu.ops.field import LIMB_BITS, NLIMBS, Field


def interpret_mode() -> bool:
    """Whether pallas_call runs the kernels of this dialect in the
    Pallas interpreter: only on the CPU backend (the tier-1 tests),
    which has no Mosaic. Every kernel asks here, so a chip entry point
    can check once that the answer is False."""
    return jax.default_backend() == "cpu"


def const_col(limbs, b: int):
    """Materialize limb constants as an (n, b) int32 array in-trace.

    limbs: tuple of Python ints (one per sublane row). Built from iota +
    scalar where-chains so Mosaic sees instructions, not captured arrays.
    """
    n = len(limbs)
    i = jax.lax.broadcasted_iota(jnp.int32, (n, b), 0)
    out = jnp.zeros((n, b), jnp.int32)
    for idx, v in enumerate(limbs):
        if v:
            out = jnp.where(i == idx, jnp.int32(v), out)
    return out


class FieldLF:
    """Limbs-first view over a Field's constants."""

    def __init__(self, f: Field):
        self.f = f
        self.p = f.p
        # constants as Python int tuples; materialized in-trace on use
        self.fold260_t = tuple(int(x) for x in f.fold260)
        self.fold_top_t = tuple(int(x) for x in f.fold_top)
        self.bias64p_t = tuple(int(x) for x in f.bias64p)
        self.p_t = tuple(int(x) for x in f.p_limbs)
        self.shift_top = f.shift - LIMB_BITS * (NLIMBS - 1)
        # Static bound bookkeeping for the cheap-carry fast paths.
        # fold_sum bounds the value added to low limbs per unit of top carry.
        self.fold_sum = sum(m for _, m in f.fold_pairs)
        # Fast-mode invariant: every field element limb satisfies
        # |limb| <= B1 = 2^13 + 3*(1 + fold_sum). Induction: adding two such
        # values gives |s| <= 2*B1 < 2^14.4, whose 1-pass carry c satisfies
        # |c| <= 3 (floor shift), so limb0 <= 2^13-1 + 3*fold_sum and other
        # limbs <= 2^13-1 + 3 — both within B1. The mode is legal iff
        # schoolbook columns still fit int32: NLIMBS * B1^2 < 2^31.
        # ed25519 (fold 608): B1 = 10019, 20*B1^2 = 2.007e9 < 2^31 -> fast.
        # secp256k1 (fold 8465): B1 = 33590 -> 2.26e10, stays on slow path.
        self.bound1 = (1 << LIMB_BITS) + 3 * (1 + self.fold_sum)
        self.fast = NLIMBS * self.bound1 * self.bound1 < 2**31

    def const_limbs(self, v: int):
        """Field constant v as a limb tuple (for const_col at call sites)."""
        return tuple(int(x) for x in self.f.from_int(v))

    def one_col(self, like):
        """The field element 1 with the same (NLIMBS, B) shape as `like`."""
        return const_col((1,) + (0,) * (NLIMBS - 1), like.shape[1])

    # -- carries --------------------------------------------------------------

    def carry(self, x):
        """Two-pass parallel carry; see field.Field.carry for the contract."""
        b = x.shape[1]
        c = x >> LIMB_BITS
        x = x - (c << LIMB_BITS)
        x = x + jnp.pad(c[:-1], ((1, 0), (0, 0)))
        x = x + c[-1:] * const_col(self.fold260_t, b)
        c = x >> LIMB_BITS
        mask = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) < NLIMBS - 1
        c = jnp.where(mask, c, 0)  # keep the (tiny) top residual in place
        x = x - (c << LIMB_BITS)
        return x + jnp.pad(c[:-1], ((1, 0), (0, 0)))

    def carry1(self, x):
        """Single-pass parallel carry + top fold. Valid for |limb| <= 2*B1
        (post add/sub values); restores the B1 invariant (see __init__)."""
        c = x >> LIMB_BITS
        x = x - (c << LIMB_BITS)
        x = x + jnp.pad(c[:-1], ((1, 0), (0, 0)))
        return x + c[-1:] * const_col(self.fold260_t, x.shape[1])

    def add(self, a, b):
        s = a + b
        return self.carry1(s) if self.fast else self.carry(s)

    def sub(self, a, b):
        s = a - b
        return self.carry1(s) if self.fast else self.carry(s)

    def neg(self, a):
        return -a

    def mul_small(self, a, k: int):
        assert 0 < abs(k) < 2**17
        if self.fast and abs(k) <= 2:
            return self.carry1(a * jnp.int32(k))
        return self.carry(self.carry(a * jnp.int32(k)))

    # -- multiply -------------------------------------------------------------
    #
    # NOTE: no `.at[slice].add()` anywhere in this module — it lowers to
    # scatter-add whose (often empty) index array becomes a captured
    # constant that Pallas rejects, and Mosaic has no scatter anyway.
    # Offset accumulation is expressed as pad+add instead.

    @staticmethod
    def _place(x, off: int, width: int):
        """Embed x (k, B) at row offset off inside a (width, B) zero buffer."""
        k = x.shape[0]
        assert off >= 0 and off + k <= width
        if k == width:
            return x
        return jnp.pad(x, ((off, width - off - k),) + ((0, 0),) * (x.ndim - 1))

    def mul(self, a, b):
        wide = 2 * NLIMBS - 1
        acc = None
        for i in range(NLIMBS):
            term = self._place(a[i : i + 1] * b, i, wide)
            acc = term if acc is None else acc + term
        return self._reduce_wide(acc)

    def square(self, a):
        """Schoolbook square using symmetry: ~half the partial products."""
        wide = 2 * NLIMBS - 1
        acc = None
        for i in range(NLIMBS):
            # diagonal term
            term = self._place(a[i : i + 1] * a[i : i + 1], 2 * i, wide)
            acc = term if acc is None else acc + term
            # off-diagonal doubled terms j > i
            if i + 1 < NLIMBS:
                acc = acc + self._place(
                    (2 * a[i : i + 1]) * a[i + 1 :], 2 * i + 1, wide
                )
        return self._reduce_wide(acc)

    def _pcarry_wide(self, x):
        c = x >> LIMB_BITS
        x = x - (c << LIMB_BITS)
        n = x.shape[0]
        pad0 = ((0, 0),) * (x.ndim - 1)
        return jnp.pad(x, ((0, 1),) + pad0) + jnp.pad(c, ((1, 0),) + pad0)

    def _reduce_wide(self, acc):
        if self.fast:
            # 1 pcarry (cols -> <2^18) + single fold + 2x carry1 restores
            # the B1 invariant. Bound chain (ed25519, fold 608): cols
            # <= 2.01e9 -> pcarry limbs <= 253k -> fold <= 1.54e8 ->
            # carry1 A: limbs <= 27k except limb0 <= 11.4M -> carry1 B:
            # limb0 < 2^13+1824, limb1 <= 9587, rest <= 8194 — all <= B1.
            assert self.f.max_off == 0, "fast path assumes 1-limb fold"
            acc = self._pcarry_wide(acc)
            high = acc[NLIMBS:]
            buf = acc[:NLIMBS]
            for off, m in self.f.fold_pairs:
                buf = buf + self._place(high * jnp.int32(m), off, NLIMBS)
            return self.carry1(self.carry1(buf))
        guard = 0
        while acc.shape[0] > NLIMBS:
            guard += 1
            assert guard < 8
            acc = self._pcarry_wide(acc)
            acc = self._pcarry_wide(acc)
            high = acc[NLIMBS:]
            low = acc[:NLIMBS]
            nh = high.shape[0]
            w = max(NLIMBS, self.f.max_off + nh)
            buf = self._place(low, 0, w)
            for off, m in self.f.fold_pairs:
                buf = buf + self._place(high * jnp.int32(m), off, w)
            acc = buf
        return self.carry(self.carry(acc))


    # -- exponentiation -------------------------------------------------------

    def pow2k(self, x, k: int):
        """x^(2^k) by k squarings (fori_loop)."""
        return jax.lax.fori_loop(0, k, lambda _, v: self.square(v), x)

    def pow_p58(self, x):
        """x^((p-5)/8) for p = 2^255-19, i.e. x^(2^252 - 3).

        Classic ladder (ref10-style): build x^(2^250-1) from doubling
        chains, then two squarings and a final multiply.
        """
        x2 = self.mul(self.square(x), x)  # 2^2 - 1
        x4 = self.mul(self.pow2k(x2, 2), x2)  # 2^4 - 1
        x5 = self.mul(self.square(x4), x)  # 2^5 - 1
        x10 = self.mul(self.pow2k(x5, 5), x5)
        x20 = self.mul(self.pow2k(x10, 10), x10)
        x40 = self.mul(self.pow2k(x20, 20), x20)
        x50 = self.mul(self.pow2k(x40, 10), x10)
        x100 = self.mul(self.pow2k(x50, 50), x50)
        x200 = self.mul(self.pow2k(x100, 100), x100)
        x250 = self.mul(self.pow2k(x200, 50), x50)
        return self.mul(self.pow2k(x250, 2), x)  # 2^252 - 3

    # -- canonicalization -----------------------------------------------------

    def canonical(self, x):
        b = x.shape[1]
        x = x + const_col(self.bias64p_t, b)
        fold_top = const_col(self.fold_top_t, b)
        for _ in range(2):
            x = self._ripple(x)
            hi = x[-1:] >> self.shift_top
            x = x - self._place(hi << self.shift_top, NLIMBS - 1, NLIMBS)
            x = x + hi * fold_top
        x = self._ripple(x)
        t = self._ripple(x - const_col(self.p_t, b))
        neg = t[-1:] < 0
        return jnp.where(neg, x, t)

    def _ripple(self, x):
        rows = []
        c = jnp.zeros_like(x[0:1])
        for i in range(NLIMBS):
            v = x[i : i + 1] + c
            if i < NLIMBS - 1:
                c = v >> LIMB_BITS
                v = v - (c << LIMB_BITS)
            rows.append(v)
        return jnp.concatenate(rows, axis=0)

    def is_zero(self, x):
        """(NLIMBS, B) -> (1, B) bool."""
        return jnp.all(self.canonical(x) == 0, axis=0, keepdims=True)

    def eq(self, a, b):
        return self.is_zero(a - b)

    def parity(self, x):
        """(NLIMBS, B) -> (1, B) int32 LSB of the canonical value."""
        return self.canonical(x)[0:1] & 1
