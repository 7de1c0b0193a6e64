"""Rows from a seed and LIBSVM text from arrays, vectorised.

A configuration hands over the rows as arrays (labels, feature ids, values)
and how its source prints a value; this writes them as ``label id:value ...``
lines in natural width (no zero or space padding), a few hundred MB a second.

Method: every row is laid out in a fixed-width byte matrix (label, then per
entry ``' ' + id digits + ':' + value text``) with unused positions left 0,
and the zeros are dropped by one boolean-mask copy. Ids print through a
table of 4-digit groups. Row chunks are built on a few threads (numpy
releases the GIL in these loops) and written in order.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

_CHUNK_ROWS = 1 << 15
_THREADS = 4
_ID_WIDTH = 8  # two 4-digit groups: ids below 10**8


_HASH = np.uint32(0x9E3779B9)  # 2^32 / golden ratio


def field_power_law_ids(rng, n: int, field_sizes, exponent: float):
    """[n, fields] int32 feature ids, one of each field a row. The fields
    partition ids 1..sum(field_sizes) in order; within a field the rank is
    drawn from p(rank) ~ rank^-exponent (inverse CDF of the continuous law
    on [1, size + 1), in float32) and scattered over the field by a
    multiplicative hash, so that popular ids are not neighbours (a few
    ranks share an id, as in any hashed feature space)."""
    sizes = np.asarray(field_sizes, dtype=np.float64)
    lows = (1 + np.concatenate([[0], np.cumsum(sizes)[:-1]])).astype(np.int32)
    last = (sizes - 1).astype(np.int32)
    scale = (sizes / 2.0 ** 32).astype(np.float32)
    top = ((sizes + 1.0) ** (1.0 - exponent) - 1.0).astype(np.float32)
    power = np.float32(1.0 / (1.0 - exponent))
    u = rng.random((n, len(sizes)), dtype=np.float32)
    out = np.empty(u.shape, dtype=np.int32)

    def fill(lo):
        rank = (top * u[lo: lo + _CHUNK_ROWS] + np.float32(1)) ** power
        mixed = rank.astype(np.uint32) * _HASH  # wraps: that is the hash
        within = (mixed.astype(np.float32) * scale).astype(np.int32)
        out[lo: lo + _CHUNK_ROWS] = lows + np.minimum(within, last)

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, range(0, n, _CHUNK_ROWS)))
    return out


def _group_tables():
    """(full, lead): the 4 ASCII digits of 0..9999, and the same with
    leading zeros as byte 0 (0 itself keeps its last digit)."""
    n = np.arange(10000)
    full = np.stack([(n // 10 ** p) % 10 for p in (3, 2, 1, 0)], axis=1)
    full = (full + ord("0")).astype(np.uint8)
    lead = full.copy()
    for p, col in zip((1000, 100, 10), (0, 1, 2)):
        lead[n < p, col] = 0
    return full, lead


_FULL, _LEAD = _group_tables()


def _digits(ids: np.ndarray) -> np.ndarray:
    """[..., 8] ASCII digits of ``ids`` in natural width: the positions
    before the first digit hold byte 0."""
    hi, lo = np.divmod(ids.astype(np.uint32), np.uint32(10000))
    out = np.empty(ids.shape + (_ID_WIDTH,), dtype=np.uint8)
    out[..., :4] = _LEAD[hi]
    out[..., 4:] = np.where((hi == 0)[..., None], _LEAD[lo], _FULL[lo])
    out[..., :4][hi == 0] = 0
    return out


def value_pool(values: np.ndarray, fmt: str) -> np.ndarray:
    """[len(values), W] byte table of each value printed with ``fmt``
    (python's ``%`` formatting, done once per pool entry), 0-padded."""
    texts = [(fmt % float(v)).encode() for v in values]
    width = max(len(t) for t in texts)
    table = np.zeros((len(texts), width), dtype=np.uint8)
    for i, t in enumerate(texts):
        table[i, : len(t)] = np.frombuffer(t, dtype=np.uint8)
    return table


def _chunk_bytes(label, ids, value_text, pool_index) -> np.ndarray:
    rows, k = ids.shape
    const = isinstance(value_text, bytes)
    val_width = len(value_text) if const else value_text.shape[1]
    slot = 1 + _ID_WIDTH + 1 + val_width
    buf = np.zeros((rows, 1 + k * slot + 1), dtype=np.uint8)
    buf[:, 0] = label.astype(np.uint8) + ord("0")
    buf[:, -1] = ord("\n")
    entries = buf[:, 1:-1].reshape(rows, k, slot)
    entries[:, :, 0] = ord(" ")
    entries[:, :, 1: 1 + _ID_WIDTH] = _digits(ids)
    entries[:, :, 1 + _ID_WIDTH] = ord(":")
    if const:
        entries[:, :, 2 + _ID_WIDTH:] = np.frombuffer(value_text, np.uint8)
    else:
        entries[:, :, 2 + _ID_WIDTH:] = value_text[pool_index]
    flat = buf.ravel()
    return flat[flat != 0]


def write_libsvm(path: str, label: np.ndarray, ids: np.ndarray,
                 value_text, pool_index: np.ndarray = None) -> int:
    """Write ``label id:value ...`` lines; returns the bytes written.

    ``label`` [n] of 0/1, ``ids`` [n, k] ints in [0, 10**8).
    ``value_text`` is either ``bytes`` (every value prints as that text) or
    a byte table from :func:`value_pool`, indexed per entry by
    ``pool_index`` [n, k]."""
    n = ids.shape[0]
    if ids.size and (ids.min() < 0 or ids.max() >= 10 ** _ID_WIDTH):
        raise ValueError("feature ids must lie in [0, 10**8)")

    def build(lo):
        hi = min(n, lo + _CHUNK_ROWS)
        index = None if pool_index is None else pool_index[lo:hi]
        return _chunk_bytes(label[lo:hi], ids[lo:hi], value_text, index)

    total = 0
    with open(path, "wb") as out, ThreadPoolExecutor(_THREADS) as pool:
        for data in pool.map(build, range(0, n, _CHUNK_ROWS)):
            out.write(memoryview(data))
            total += data.size
    return total
