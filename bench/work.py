"""The work the benchmark credits to an operation, from shapes alone.

`ring_matmul_work(m, K, n)` is the least an exact product of an (m, K) by a
(K, n) matrix of Z_2^64 words must do, whatever implements it: read both
operands and write the result once, 8 bytes a word, and form the 36
contributing int8 x int8 digit products of 2mKn operations each (signed
base-256 digits, products at bit 8(i+j) < 64). `triple_bytes` counts the
correlated randomness a compiled launch takes as arguments.
"""
from __future__ import annotations

import dataclasses
import math

WORD = 8
LIMBS = 8
DIGIT_PRODUCTS = LIMBS * (LIMBS + 1) // 2      # i + j <= 7: 36


@dataclasses.dataclass(frozen=True)
class Work:
    bytes: float = 0.0
    ops: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.bytes + other.bytes, self.ops + other.ops)


def ring_matmul_work(m: int, k: int, n: int, batch: int = 1) -> Work:
    return Work(bytes=float(WORD * (m * k + k * n + m * n) * batch),
                ops=float(DIGIT_PRODUCTS * 2 * m * k * n * batch))


def ring_matmul_from_digit_dot(lhs: int, rhs: int, out: int) -> Work:
    """The ring product behind one digit dot, from its element counts.

    A digit dot of an exact ring product takes the 8 digit planes of each
    operand, 8mK and 8Kn int8 elements, and forms all 64 plane products,
    64mn int32 elements, however its dimensions are laid out. So
    mK = lhs/8, Kn = rhs/8, mn = out/64, and mKn = sqrt of their product.
    A batched product (batch B) reads as one of sqrt(B) times the
    operations; the bytes come out exact either way."""
    mk, kn, mn = lhs / LIMBS, rhs / LIMBS, out / (LIMBS * LIMBS)
    if min(mk, kn, mn) <= 0:
        return Work()
    return Work(bytes=float(WORD * (mk + kn + mn)),
                ops=float(DIGIT_PRODUCTS * 2 * math.sqrt(mk * kn * mn)))


def triple_bytes(requests) -> int:
    """Bytes of the correlated randomness one launch consumes, for a
    program's offline `requests` [(kind, shape)]: a matmul triple of
    ((m, K), (K, n)) is two shares each of U (m, K), V (K, n) and
    Z (m, n); a multiplication or binary triple of a shape is six words
    per element; `rand` is one word per element."""
    words = 0
    for kind, shape in requests:
        if kind == "matmul":
            (m, k), (_k2, n) = shape
            words += 2 * (m * k + k * n + m * n)
        elif kind in ("mul", "bin"):
            words += 6 * math.prod(shape)
        else:
            words += math.prod(shape)
    return WORD * words
