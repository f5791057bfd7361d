"""The paper's fused-MAC processing element, rebuilt bit by bit.

An N-bit signed multiplier is a Baugh-Wooley carry-save array: row ``i``
adds the partial products ``a_j b_i`` at column ``i + j``; the sign row and
the sign column hold negated-product cells (NPPC), the rest positive ones
(PPC), and the constant ``2^N - 2^(2N-1)`` seeds the sum word. Each row is
absorbed into a (sum, carry) word pair by one cell per column; carries move
one column left for the next row, and a final carry-propagate add gives the
product. Columns ``< k`` use the approximate cells of the paper's Table I:

* approximate PPC:  C = p,               S = (S_in | C_in) & ~p
* approximate NPPC: C = (S_in | C_in) & ~p,  S = ~C

every other column an exact full adder (a half adder where no cell sits).
"""
from __future__ import annotations

import functools

import numpy as np


def _cells(n_bits: int):
    """Per row: {column: (a_bit, b_bit, negated)}."""
    n = n_bits
    rows = []
    for i in range(n - 1):
        row = {i + j: (j, i, False) for j in range(n - 1)}
        row[i + n - 1] = (n - 1, i, True)
        rows.append(row)
    last = {j + n - 1: (j, n - 1, True) for j in range(n - 1)}
    last[2 * n - 2] = (n - 1, n - 1, False)
    rows.append(last)
    return rows


@functools.lru_cache(maxsize=8)
def product_table(n_bits: int = 8, k: int = 0, acc_bits: int = 24) -> np.ndarray:
    """(2^N, 2^N) int64 table of the PE's product, indexed by the operands'
    two's-complement bit patterns."""
    span = 1 << n_bits
    a = np.repeat(np.arange(span, dtype=np.int64), span)
    b = np.tile(np.arange(span, dtype=np.int64), span)
    abit = [(a >> j) & 1 for j in range(n_bits)]
    bbit = [(b >> j) & 1 for j in range(n_bits)]
    const = (2 ** n_bits - 2 ** (2 * n_bits - 1)) % (2 ** acc_bits)
    s = [np.full(a.shape, (const >> w) & 1, np.int64) for w in range(acc_bits)]
    c = [np.zeros(a.shape, np.int64) for _ in range(acc_bits)]
    for row in _cells(n_bits):
        s_new, c_out = [], []
        for w in range(acc_bits):
            cell = row.get(w)
            if cell is None:
                e, approx, neg = 0, False, False
            else:
                ja, ib, neg = cell
                p = abit[ja] & bbit[ib]
                e = p ^ 1 if neg else p
                approx = w < k
            if approx and not neg:           # approximate PPC (e = p)
                sw = (s[w] | c[w]) & (e ^ 1)
                cw = e
            elif approx:                     # approximate NPPC (e = ~p)
                cw = (s[w] | c[w]) & e
                sw = cw ^ 1
            else:                            # exact full adder
                x = s[w] ^ e
                sw = x ^ c[w]
                cw = (s[w] & e) | (c[w] & x)
            s_new.append(sw)
            c_out.append(cw)
        s = s_new
        c = [np.zeros(a.shape, np.int64)] + c_out[:-1]
    total = sum(s[w] << w for w in range(acc_bits)) + sum(
        c[w] << w for w in range(acc_bits))
    total &= (1 << acc_bits) - 1
    total = np.where(total >= 1 << (acc_bits - 1), total - (1 << acc_bits),
                     total)
    return total.reshape(span, span)


def signed_values(n_bits: int = 8) -> np.ndarray:
    span = 1 << n_bits
    v = np.arange(span, dtype=np.int64)
    return np.where(v >= span >> 1, v - span, v)


@functools.lru_cache(maxsize=8)
def error_tile(n_bits: int = 8, k: int = 2, acc_bits: int = 24) -> np.ndarray:
    """(2^k, 2^k) table of ``product - a*b`` by the operands' low k bits.

    Raises if the error is not a function of the low k bits alone, which is
    what lets the reference add it with one-hot integer matmuls."""
    t = product_table(n_bits, k, acc_bits)
    v = signed_values(n_bits)
    err = t - v[:, None] * v[None, :]
    m = (1 << k) - 1
    span = 1 << n_bits
    idx = np.arange(span) & m
    tile = err[:1 << k, :1 << k]
    if not np.array_equal(err, tile[idx][:, idx]):
        raise AssertionError(f"PE error at N={n_bits}, k={k} is not a "
                             "function of the operands' low k bits")
    return tile
