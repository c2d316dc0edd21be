"""Per-row lookup in a small table without a gather.

`table[ids]` with `ids` of row length and a table of at most a few
thousand entries is one XLA gather, and XLA's gather on the TPU walks the
rows one element at a time: 9 ns a row where reading the ids and writing
the result is 0.01 ns. `row_lookup` gives the same bits by a one-hot
contraction on the MXU.

The lookup moves bits, not numbers. The table's 32-bit patterns are cut
into four 8-bit digits; integers 0..255 and a one-hot row are exact in
bfloat16 and the float32 accumulator adds one non-zero term, so the
contraction returns each digit exactly and shifts put the word back:
zero, -0.0, denormals, Inf and NaN payloads come out as they went in, on
every backend.

The index is cut in two as well, `id = hi * LO + lo`: one one-hot of `lo`
against the table laid out `[hi_n * 4, LO]`, then a select over the
`hi_n` candidates by compare (one candidate for a table of at most `LO`
entries).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

# Both constants were read on a TPU v5e over 25,165,824 rows, where the
# gathers took 217 and 246 ms (PR 29's sweep, PERF.md section 6).

# Entries one one-hot spans, the low digit of the index: a 3,112-entry
# table takes 98 / 37 / 21 / 17 ms at 32 / 64 / 128 / 256, a 255-entry
# one 9-10 ms at any of them.
LO = 256
# Rows a step of the row loop looks up: keeps the `[LO, BLOCK]` one-hot
# and the `[hi_n * 4, BLOCK]` float32 digits to a few MB whatever the row
# count. Blocks of 16,384 to 65,536 rows read within 10% of each other,
# 262,144 up to 2.2x slower.
BLOCK = 16384


def row_lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """`table[ids]` for a 1-D `table` of a 32-bit dtype and 1-D integer
    `ids` in `[0, len(table))`, equal to the gather to the bit. An id
    outside the range reads the all-zero pattern (callers clip, as they
    did for the gather). Traces under `jax.vmap` and inside `shard_map`
    (no collective)."""
    (m,), (n,) = table.shape, ids.shape
    if table.dtype.itemsize != 4:
        raise TypeError(f"row_lookup moves 32-bit words, not {table.dtype}")
    hi_n = -(-m // LO)
    bits = lax.bitcast_convert_type(table, jnp.uint32)
    bits = jnp.pad(bits, (0, hi_n * LO - m)).reshape(hi_n, 1, LO)
    # [hi_n * 4, LO]: row `h * 4 + d` holds digit `d` of entries h*LO..
    digits = ((bits >> (8 * jnp.arange(4, dtype=jnp.uint32))[:, None])
              & 0xFF).reshape(hi_n * 4, LO).astype(jnp.bfloat16)
    lo_iota = jnp.arange(LO, dtype=ids.dtype)[:, None]
    hi_iota = jnp.arange(hi_n, dtype=ids.dtype)[:, None, None]
    block = min(BLOCK, n)

    def one_block(idb):
        # rows on the minor axis throughout
        onehot = (idb % LO == lo_iota).astype(jnp.bfloat16)
        d = jnp.dot(digits, onehot, preferred_element_type=jnp.float32)
        d = d.reshape(hi_n, 4, block)
        w = jnp.where(idb // LO == hi_iota, d, 0.0).sum(axis=0)
        w = w.astype(jnp.uint32)
        return w[0] | (w[1] << 8) | (w[2] << 16) | (w[3] << 24)

    blocks = -(-n // block)
    padded = jnp.pad(ids, (0, blocks * block - n)).reshape(blocks, block)
    words = lax.map(one_block, padded).reshape(-1)[:n]
    return lax.bitcast_convert_type(words, table.dtype)
