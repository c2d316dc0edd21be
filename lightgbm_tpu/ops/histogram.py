"""Leaf histogram construction — the hottest op in GBDT training.

TPU-native replacement for the reference's gather-accumulate loops
(`DenseBin::ConstructHistogram`, src/io/dense_bin.hpp:66-133 — the CPU hot
loop — and the OpenCL `histogram256` kernels,
src/treelearner/ocl/histogram256.cl:345-790).

Design (SURVEY.md §7): rows carry a `leaf_id`; the histogram of one leaf is
a masked reduction over ALL rows:

    hist[f, b, c] = sum_r  1[bin[r, f] == b] * w[r, c]

with channels c = (grad*m, hess*m, m) and m the leaf/bagging mask. The
one-hot compare `bin == iota` turns the scatter-add (which TPUs serialize)
into a dense contraction that XLA fuses and the MXU executes: per row-chunk
an einsum `[C,F,B] x [C,S] -> [F,B,S]`. Chunking via `lax.scan` bounds the
materialized one-hot to VMEM-friendly sizes and gives f32 accumulation
across chunks (the reference accumulates in f64, bin.h:29-33; chunked f32
keeps 10M-row sums within tolerance).

Two performance levers over the naive contraction:
- `bf16=True` runs the MXU in bf16 with the weights split into hi+lo
  bf16 halves, FUSED into a single contraction: the count channel's 0/1
  values are bf16-exact (lo == 0), so the lo correction rides along as
  2 extra grad/hess channels per child slot. grad/hess recover ~16
  mantissa bits — within f32 round-off of the true sum — at bf16 MXU
  rates.
- `batched_leaves_histogram` — the in-training kernel — builds the
  histograms of 2K child nodes of the speculative grower
  (learner/grow.py) in ONE pass by widening the contraction's output
  dimension from 3 to 2K*3 (+2K*2 lo-correction) channels. The MXU's
  output tile is 128 lanes whether 5 or 128 of them are live, so the
  grower sizes 2K*(3+2) to fill the tile (batch_k=12) — extra slots
  are free, and the per-pass cost sits at ~70% of the bf16 matmul
  roofline (profiles/README.md).
- `gathered_leaves_histogram` breaks the remaining O(N)-per-pass floor
  for SMALL nodes: late in a tree the expanded nodes hold ~1% of the
  rows, yet the full-pass kernels still contract every chunk. The
  grower compacts the member rows' indices into a fixed-capacity
  buffer and this kernel contracts only the gathered subset — per-node
  work scales with node size, the economics of the reference's
  DataPartition leaf index lists (data_partition.hpp:94-170).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.layers import scope


def _hi_lo(w):
    """Split f32 into two bf16s with hi+lo ~= w to f32 precision.

    The rounding to bf16 is a `reduce_precision`, which no compiler pass
    may drop. Written as `w - w.astype(bf16).astype(f32)` the round trip
    is a convert pair that XLA:TPU elides when both converts land in one
    fusion (it keeps the f32 it has: "excess precision"), and lo comes
    out 0: the gathered kernel on the chip did that once the operand was
    built another way (PR 37), and its histograms read like bf16 alone."""
    hi = jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (w - hi).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# quantized-gradient training (tpu_hist_quantize, ISSUE 20)
#
# Per-iteration grad/hess vectors are scaled and stochastically rounded to
# integers in [-qmax, qmax] (quantize_gradients below); the kernels then
# contract the integer-valued channels exactly — int8 rides the plain
# 3-channel bf16 contraction (every |v| <= 127 is bf16-exact and a chunk's
# per-bin sum stays under 2^24, the bf16-einsum f32 accumulator's exact
# range), int16 splits each value into base-256 digits hi*256 + lo with
# |digit| <= 128 (the _hi_lo layout, reused with exact integer digits
# instead of lossy bf16 halves). Cross-chunk accumulation is int32, so
# histogram merges — psum/psum_scatter, sibling subtraction, compaction —
# are associative-exact: any reduction order gives the same bits, which is
# what keeps scatter == serial bitwise in the quantized modes.
# ---------------------------------------------------------------------------

TRAIN_QUANTIZE_MODES = ("none", "int16", "int8")

_TRAIN_QMAX = {"int8": 127, "int16": 32767}


def train_qmax(mode: str, n: int) -> int:
    """Adaptive clip magnitude for quantized training at row count n.

    The int32 bin accumulators must absorb a worst-case bin holding every
    row at full magnitude: |sum q| <= qmax * n must stay below 2^31. The
    256 headroom additionally covers the int16 digit channels' worst-case
    carry (256 * sum hi <= sum|q| + 128n, and once the cap forces
    qmax < 128 the hi digit is identically zero). Small datasets get the
    full type range; huge ones degrade precision gracefully — the
    accuracy gate (gbdt._hist_quant_gate) judges whether the surviving
    precision is acceptable."""
    cap = (2 ** 31 - 1) // max(1, int(n)) - 256
    return max(1, min(_TRAIN_QMAX[mode], cap))


def _digits(w):
    """Split integer-valued f32 (|w| <= 32767) into base-256 digits:
    w == hi * 256 + lo with both digits integer-valued in [-128, 128] —
    every digit is bf16-exact, so the bf16 einsum contracts them with
    zero rounding error."""
    hi = jnp.round(w * (1.0 / 256.0))
    lo = w - 256.0 * hi
    return hi, lo


def stochastic_round(x, key, n: int):
    """Stochastically round f32 [n_pad] to integer-valued f32.

    The uniform is drawn over the SERIAL shape (n,) and padded — a
    (n_pad,) draw would tie the rounding to the padded row count (a
    function of device count; threefry is not prefix-stable across
    shapes) and break cross-world-size bit-identity, the PR 11 bagging
    bug class. Padding rows carry x == 0 (zero channels contract to
    zero), and floor(0) + (0 < 0) == 0 keeps them at zero."""
    n_pad = x.shape[0]
    u = jax.random.uniform(key, (n,))
    if n_pad > n:
        u = jnp.pad(u, (0, n_pad - n))
    f = jnp.floor(x)
    return f + (u < (x - f)).astype(jnp.float32)


# scale floor: an all-zero gradient vector must not divide by zero; any
# positive subnormal-free floor works (the quantized values are then 0)
_SCALE_FLOOR = jnp.float32(1e-30)


def quantize_gradients(grad, hess, row_weight, *, n: int, qmax: int,
                       key_g, key_h, hess_const=False):
    """Quantize one class's gradient/hessian vectors for histogram work.

    Bagging/GOSS weights fold in BEFORE quantization (gw = grad * rw), so
    amplified rows quantize at their amplified magnitude and the returned
    row weight collapses to the 0/1 in-bag indicator — grow_tree's
    channel build (q * w01) then keeps every channel integer-valued.

    hess_const (python bool or traced scalar): with a constant hessian
    and 0/1 row weights every in-bag row's hw is the same value, so the
    deterministic q_h = qmax * w01 is EXACT (per-bin hess == qmax * count
    in the integer domain — the identity the constant-hessian collective
    elision in learner/grow.py relies on) and needs no rounding key.

    Returns (q_g, q_h, w01, qscale): integer-valued f32 vectors in
    [-qmax, qmax], the 0/1 in-bag weight, and the [3] dequantization
    scale (g_scale, h_scale, 1.0) with q * scale ~= the real-unit value.
    """
    qm = jnp.float32(qmax)
    w01 = (row_weight > 0).astype(jnp.float32)
    gw = grad * row_weight
    hw = hess * row_weight
    g_scale = jnp.maximum(jnp.max(jnp.abs(gw[:n])), _SCALE_FLOOR) / qm
    h_scale = jnp.maximum(jnp.max(jnp.abs(hw[:n])), _SCALE_FLOOR) / qm
    q_g = jnp.clip(stochastic_round(gw / g_scale, key_g, n), -qm, qm)
    q_h_sr = jnp.clip(stochastic_round(hw / h_scale, key_h, n), -qm, qm)
    q_h = jnp.where(hess_const, qm * w01, q_h_sr)
    qscale = jnp.stack([g_scale, h_scale, jnp.float32(1.0)])
    return q_g, q_h, w01, qscale


# one-hot working-set budget per (row-chunk x group-block) contraction step,
# in elements; bounds the materialized [chunk, Gb, Bb] operand
_BLOCK_BUDGET = 1 << 26

# the most bins a group reaches the matmul with: a wider group is
# contracted as sub-groups of at most this many bins (_group_split)
SUB_BINS = 64


def _group_split(width: int):
    """(k, s): a group presented at `width` bins is contracted as k
    sub-groups of s bins, k * s >= width; (1, width) up to SUB_BINS.

    XLA:TPU lays a one-hot of more than ~96 bins out bins-minor and its
    matmul's output `{1,2,0}`, and runs it at a fifth of the rate the
    same matmul gets at 63 bins (batched_leaves_histogram's fifth design
    choice). s = ceil(width / k) rather than SUB_BINS keeps a 65-bin
    group at 66 columns, not 128; at 255 bins both are 64."""
    width = max(1, int(width))
    k = -(-width // SUB_BINS)
    return k, -(-width // k)


def plan_group_blocks(group_widths, chunk: int,
                      budget: int = _BLOCK_BUDGET):
    """Partition the stored-group axis into contiguous blocks, each
    contracted at its own static bin width.

    This replaces the round-3 scheme of shrinking the ROW chunk as
    G*B grows (which at Epsilon-like G*B ~ 128k collapsed the chunk to
    512 rows and exploded the sequential pass count): the row chunk
    stays constant and the FEATURE-GROUP axis is tiled instead. Each
    block scans at bin width = max(group widths inside it), so narrow
    features (the reference's 4-bit path, src/io/dense_nbits_bin.hpp)
    pay a proportionally narrower one-hot, not the global max width.
    A group wider than SUB_BINS is budgeted at the k * s columns its
    sub-groups take (_group_split).

    Returns a tuple of (g_start, g_count, bin_width) covering all groups.
    """
    def cols(bw):
        k, s = _group_split(bw)
        return k * s

    g = len(group_widths)
    if g == 0:
        return ()
    blocks = []
    i = 0
    while i < g:
        bw = max(1, int(group_widths[i]))
        j = i + 1
        while j < g:
            nbw = max(bw, int(group_widths[j]))
            if cols(nbw) * (j + 1 - i) * chunk > budget:
                break
            bw = nbw
            j += 1
        blocks.append((i, j - i, bw))
        i = j
    return tuple(blocks)


def plan_contraction(group_widths, chunk: int, num_bins: int):
    """The kernels' block plan: (g_start, g_count, width, k, s) a block,
    width = the block's bins as presented (min(bin_width, num_bins)),
    contracted as k sub-groups of s bins a group (_group_split)."""
    out = []
    for gs, gc, bw in plan_group_blocks(group_widths, chunk):
        w = min(bw, num_bins)
        out.append((gs, gc, w) + _group_split(w))
    return tuple(out)


def contraction_counters(group_widths, chunk: int, num_bins: int) -> dict:
    """`schedule_info["hist"]`, from the plan the kernels take:
    `split_groups`, the stored groups contracted as sub-groups;
    `sub_width`, the most bins a sub-group holds; `onehot_columns`, the
    one-hot columns a row is contracted over, summed over blocks."""
    plan = plan_contraction(group_widths, chunk, num_bins)
    return {"split_groups": sum(gc for _, gc, _, k, _ in plan if k > 1),
            "sub_width": SUB_BINS,
            "onehot_columns": sum(gc * k * s for _, gc, _, k, s in plan)}


def _contract_block_parts(get_block, blocks, u, bf16):
    """One row-chunk's histogram contribution, group-block tiled.

    get_block(gs, gc): returns the chunk's [chunk, gc] bin slice for the
    group block starting at gs — a dynamic slice of the resident bin
    matrix for the full-pass kernels, a static slice of an already
    gathered chunk for the compacted kernel.
    u: [chunk, S] channel matrix (already masked/hi-lo-packed by the
    caller). Each block materializes only a [chunk, k * Gb, s] one-hot
    (plan_contraction). Returns a TUPLE of per-block [k * Gb, s, S] f32
    parts at their OWN widths — the chunk loop accumulates the ragged
    parts and only _assemble_blocks lays them out at the uniform output
    width once, after the loop. (Padding inside the loop made the fori
    carry [G, Bmax, S]: on heavily-bundled data like the Bosch shape
    that is ~3.5x the real bin mass, all of it read and written every
    chunk step.)

    A block of k > 1 sub-groups a group: sub-group j of a group holds its
    bins s*j .. s*j + s - 1, as the group's bins less s*j in their own
    integer type; a bin below wraps to s or above (k * s <= 256 where the
    bins are uint8) or goes negative, a bin above stays s or above, and
    neither matches the sub-group's one-hot. The k shifted copies are
    joined sub-group-major, [chunk, k * Gb]: XLA:TPU builds that in one
    fusion, where the group-major [chunk, Gb, k] took a broadcast and a
    reshape of their own besides."""
    parts = []
    for gs, gc, _, k, s in blocks:
        b = get_block(gs, gc)
        if k > 1:
            b = jnp.concatenate([b - jnp.asarray(s * j, b.dtype)
                                 for j in range(k)], axis=1)
        oh = _onehot(b, s)
        if bf16:
            p = jnp.einsum("cfb,cs->fbs", oh.astype(jnp.bfloat16),
                           u.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
        else:
            p = jnp.einsum("cfb,cs->fbs", oh.astype(jnp.float32),
                           u.astype(jnp.float32),
                           preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.HIGHEST)
        parts.append(p)
    return tuple(parts)


def _chunk_blocks(binned, c, chunk):
    """get_block of the full-pass kernels: chunk c's rows of a group
    block, a dynamic slice of the resident bin matrix."""
    return lambda gs, gc: jax.lax.dynamic_slice(binned, (c * chunk, gs),
                                                (chunk, gc))


def _blocks_zeros(blocks, s, dtype=jnp.float32):
    return tuple(jnp.zeros((k * gc, sb, s), dtype)
                 for _, gc, _, k, sb in blocks)


def _assemble_blocks(parts, blocks, num_bins):
    """Lay the ragged per-block accumulators out at the uniform output
    width and concatenate along the group axis: [G, num_bins, S]. A
    split block's [k * Gb, s, S] (sub-group-major) becomes its groups'
    [Gb, k * s, S] in bin order, cut to the block's width."""
    out = []
    for p, (_, gc, w, k, s) in zip(parts, blocks):
        if k > 1:
            p = p.reshape(k, gc, s, p.shape[-1]).transpose(1, 0, 2, 3)
            p = p.reshape(gc, k * s, p.shape[-1])[:, :w]
        if p.shape[1] < num_bins:
            p = jnp.pad(p, ((0, 0), (0, num_bins - p.shape[1]), (0, 0)))
        out.append(p)
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)


def _onehot(binned_chunk: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    return (binned_chunk[:, :, None] ==
            jnp.arange(num_bins, dtype=binned_chunk.dtype)[None, None, :])


def _accumulate_chunks(one, n_chunks, blocks, num_bins, s, n_valid, chunk,
                       dtype=jnp.float32):
    """Shared chunk-accumulation scaffolding for both kernels: ragged
    per-block carries through the fori_loop, assembled (padded to the
    uniform width) once at the end. Quantized modes carry int32 — each
    chunk's f32 einsum output is exactly integer-valued (per-chunk sums
    stay under 2^24), so the cast loses nothing and the cross-chunk sum
    becomes order-invariant."""
    def cast(parts):
        if dtype == jnp.float32:
            return parts
        return tuple(p.astype(dtype) for p in parts)

    if n_chunks == 1:
        return _assemble_blocks(cast(one(jnp.int32(0))), blocks, num_bins)

    def body(c, accs):
        return tuple(a + p for a, p in zip(accs, cast(one(c))))

    trip = n_chunks if n_valid is None else \
        jnp.minimum((n_valid + chunk - 1) // chunk, n_chunks)
    init = _blocks_zeros(blocks, s, dtype)
    return _assemble_blocks(
        jax.lax.fori_loop(0, trip, body, init), blocks, num_bins)


def _channels(w_chunk, bf16, quantize):
    """A chunk's [chunk, 3] weight channels as the contraction's (base, lo)
    column blocks; lo is None where the path has no correction channels.

    bf16: the hi and lo bf16 halves of (g, h, cnt); the count channel is
    0/1 = bf16-exact, so only grad/hess carry a lo half. float32: the
    channels as they are. Quantized, already bf16 (exact: every entry is
    an integer of magnitude <= 128 for int16 digits, <= 127 for int8):
    int16 is [g_hi, h_hi, cnt] + [g_lo, h_lo] (the count channel is a
    raw 0/1, never digit-split), int8 is [g, h, cnt] alone — the bf16
    hi+lo slots, so the post-loop merge reuses the same slot arithmetic
    with *256 instead of +."""
    if quantize == "int16":
        hi, lo = _digits(w_chunk[:, 0:2])
        base = jnp.concatenate([hi, w_chunk[:, 2:3]], axis=1)
        return base.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)
    if quantize == "int8":
        return w_chunk.astype(jnp.bfloat16), None
    if bf16:
        hi, lo = _hi_lo(w_chunk)
        return hi, lo[:, 0:2]
    return w_chunk, None


def _n_lo(bf16, quantize):
    """Correction channels an id: the two lo halves (digits) of grad and
    hess in the bf16 hi+lo and int16 paths, none in float32 and int8."""
    return 2 if (quantize == "int16" or (quantize == "none" and bf16)) else 0


def _channel_operand(lid, ids, base, lo=None):
    """The contraction's channel operand u [chunk, S] for a chunk whose
    rows carry leaf labels `lid`: column (k, c) holds channel c of the
    rows labelled ids[k] and 0 elsewhere, in two blocks, [id0: c0 c1 c2,
    id1: ...] of `base` then [id0: lo0 lo1, id1: ...] of `lo` (the order
    `_fold_lo` reads).

    Each block is built AT [chunk, 3K] (or 2K): a compare of the labels
    against the ids repeated over their channels, a select chain over
    the row's channel columns by the column's static channel number —
    nothing chunk-sized of rank 3 is written out and relaid into the
    matmul's columns (batched_leaves_histogram's fourth design choice).
    The barrier holds the operand as a buffer of its own: without it
    XLA:TPU takes the whole producer INTO every matmul fusion of the
    chunk (2 at 28 features, 16 at 2000), which redo it once each."""
    with scope("lgbm/hist/operand"):
        zero = jnp.zeros((), base.dtype)

        def block(channels):
            n_c = channels.shape[1]
            chan = np.arange(ids.shape[0] * n_c) % n_c   # static, [K * n_c]
            val = channels[:, 0:1]
            for c in range(1, n_c):
                val = jnp.where(chan[None, :] == c, channels[:, c:c + 1], val)
            hit = lid[:, None] == jnp.repeat(ids, n_c)[None, :]
            return jnp.where(hit, val, zero)

        u = block(base) if lo is None else \
            jnp.concatenate([block(base), block(lo)], axis=1)
        return jax.lax.optimization_barrier(u)


def _fold_lo(hist, c_ids: int, quantize: str):
    """After the chunk loop: [F, B, S] in the operand's columns ->
    [F, B, c_ids, 3], the correction block (if the path has one) folded
    into grad and hess: hi + lo (the bf16 halves, f32) or hi * 256 + lo
    (the int16 digits; exact in int32 — train_qmax caps the per-row
    magnitude so the worst-case carry fits)."""
    f, b, s = hist.shape
    main = hist[:, :, :c_ids * 3].reshape(f, b, c_ids, 3)
    if s == c_ids * 3:
        return main
    hi = main[:, :, :, 0:2]
    if quantize == "int16":
        hi = hi * 256
    corr = hist[:, :, c_ids * 3:].reshape(f, b, c_ids, 2)
    return main.at[:, :, :, 0:2].set(hi + corr)


@functools.partial(jax.jit, static_argnames=("num_bins", "chunk", "bf16",
                                             "group_widths", "quantize"))
@scope("lgbm/hist/contract")
def leaf_histogram(binned: jnp.ndarray, weights: jnp.ndarray,
                   num_bins: int, chunk: int = 16384,
                   bf16: bool = True, n_valid=None,
                   group_widths=None, quantize: str = "none") -> jnp.ndarray:
    """hist[f, b, (g,h,cnt)] over rows where the mask channel is nonzero.

    Args:
      binned:  [N, F] int bin indices (N must be a multiple of `chunk`;
               pad rows with mask 0).
      weights: [N, 3] = (grad*mask, hess*mask, mask). Bagging/GOSS weights
               fold into the channels (GOSS amplification multiplies grad
               and hess, the count channel stays 0/1 — goss.hpp:87-131).
      num_bins: OUTPUT histogram width B (max bins over features).
      n_valid: optional traced row count; rows beyond it are PADDING (the
               loader pads as a suffix) and their chunks are skipped by a
               dynamic trip count — row-count buckets can then share one
               compiled signature with ~zero cost for the padding.
      group_widths: optional static tuple of per-group bin counts; the
               group axis is then tiled into blocks each scanned at its
               own width (plan_group_blocks). None = uniform num_bins.
      quantize: "none" (f32/bf16 hi+lo path), or "int16"/"int8" — the
               weight channels must then be INTEGER-VALUED f32 in
               [-train_qmax, train_qmax] (quantize_gradients); the
               contraction is exact and the histogram returns int32.

    CONTRACT: padding rows must carry all-zero `weights` channels. n_valid
    only skips WHOLE trailing chunks; the partial boundary chunk (and the
    n_chunks==1 fast path, which ignores n_valid entirely) still contract
    every row, so correctness relies on padded rows contributing zero to
    every (g, h, cnt) channel — not on the chunk-skip.

    Returns: [F, B, 3] float32 (int32 when quantized).
    """
    n, f = binned.shape
    if n % chunk != 0:
        raise ValueError(f"rows ({n}) must be padded to a multiple of chunk ({chunk})")
    q = quantize != "none"
    n_chunks = n // chunk
    widths = group_widths if group_widths else (num_bins,) * f
    blocks = plan_contraction(widths, chunk, num_bins)

    def one(c):
        w_chunk = jax.lax.dynamic_slice(weights, (c * chunk, 0), (chunk, 3))
        base, lo = _channels(w_chunk, bf16, quantize)
        u = base if lo is None else jnp.concatenate([base, lo], axis=1)
        return _contract_block_parts(_chunk_blocks(binned, c, chunk),
                                     blocks, u, bf16 or q)

    hist = _accumulate_chunks(one, n_chunks, blocks, num_bins,
                              3 + _n_lo(bf16, quantize), n_valid, chunk,
                              dtype=jnp.int32 if q else jnp.float32)
    return _fold_lo(hist, 1, quantize)[:, :, 0, :]


def _leaves_histogram(rows_of, ids, n_chunks, f, num_bins, chunk, bf16,
                      n_valid, group_widths, quantize):
    """What the full-pass and the gathered kernel share: the chunk loop
    over `rows_of(c)` -> (get_block, w_chunk, lid), a chunk's bin slices
    by group block, its [chunk, 3] channels (all zero on a row that must
    not count) and its leaf labels; the channel operand of the ids, the
    block contraction, and the fold of the correction columns. Returns
    [C, F, B, 3]."""
    q = quantize != "none"
    c_ids = ids.shape[0]
    widths = group_widths if group_widths else (num_bins,) * f
    blocks = plan_contraction(widths, chunk, num_bins)

    def one(c):
        get_block, w_chunk, lid = rows_of(c)
        base, lo = _channels(w_chunk, bf16, quantize)
        u = _channel_operand(lid, ids, base, lo)
        return _contract_block_parts(get_block, blocks, u, bf16 or q)

    hist = _accumulate_chunks(one, n_chunks, blocks, num_bins,
                              c_ids * (3 + _n_lo(bf16, quantize)),
                              n_valid, chunk,
                              dtype=jnp.int32 if q else jnp.float32)
    return _fold_lo(hist, c_ids, quantize).transpose(2, 0, 1, 3)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "chunk", "bf16",
                                    "group_widths", "quantize"))
@scope("lgbm/hist/contract")
def batched_leaves_histogram(binned: jnp.ndarray, weights: jnp.ndarray,
                             leaf_id: jnp.ndarray, ids: jnp.ndarray,
                             num_bins: int, chunk: int = 16384,
                             bf16: bool = True, n_valid=None,
                             group_widths=None,
                             quantize: str = "none") -> jnp.ndarray:
    """Histograms of C arbitrary leaf-label ids in one data pass.

    The speculative grower (learner/grow.py) relabels rows to child node
    ids BEFORE building their histograms, so membership is a direct
    `leaf_id == ids[k]` compare — no split bit. Returns [C, F, B, 3].

    Five deliberate design choices, all but the third profiled on
    hardware:
    - rows are walked with `lax.dynamic_slice` chunks instead of an
      upfront reshape to [n_chunks, chunk, F]: the reshape forced XLA to
      materialize two layout copies of the whole bin matrix per pass
      (~0.15 ms/pass at 0.5M rows — `profiles/README.md` round 2);
    - the contraction's MXU output tile is 128 lanes no matter how few
      channels are live, so C is sized by the caller to fill it
      (C*(3 hi + 2 lo) <= 128, i.e. C <= 25) — extra slots are free
      on narrow-feature data where F*B underfills the other tile axis;
    - for WIDE data the group axis is tiled into constant-row-chunk
      blocks (plan_group_blocks), each scanned at its own bin width —
      the row chunk no longer shrinks with G*B, and <=16-bin features
      get the reference 4-bit path's cost discount
      (src/io/dense_nbits_bin.hpp:1-405);
    - the channel operand u [chunk, C*5] is built AT that shape
      (`_channel_operand`: a compare against the ids repeated over
      their channels, a select chain over the row's channel columns),
      not as [chunk, C, 3] and [chunk, C, 2] products reshaped into the
      matmul's columns: XLA:TPU wrote each product out (three channels
      padded to four) and relaid it, four copy operations a chunk that
      were a fifth of a 28-feature pass (0.599 of 2.878 s of device
      time a pair of trees at 21M x 28; TPU v5e, PR 37, `PERF.md`
      section 6). Read alone at (25.2M x 28, 24 ids) / (12.6M x 137, 8
      ids) / (1M x 2000, 8 ids), ms a pass: the old form 74.8 / 107.1 /
      125.8, this one 61 / 104 / 126. What lost, so nobody tries
      it again: the same compare-and-select WITHOUT the barrier (66.8 /
      115.5 / 138.7: fused into every matmul fusion and redone there);
      widening by two constant 0/1 matmuls (67.5 / 104.8); CHANNEL-major
      columns (five [chunk, C] selects concatenated), the fastest alone
      (60.6 / 104.0 / 125.4) and cheapest to build, but in the grow
      program its [F][C][K][B] accumulators make XLA re-lay the whole
      subtraction cache every pass (0.74 s a pair at 2000 features);
    - no group reaches the matmul wider than SUB_BINS = 64 bins: a wider
      one is contracted as k sub-groups of ceil(w / k) bins
      (_group_split), sub-group j comparing the bins less s*j.
      XLA:TPU lays a one-hot of more than ~96 bins out bins-minor
      (`pred[65536,4,255]{2,0,1}`) and its matmul `{1,2,0}`: at 255
      bins (the Expo bundles, LightGBM's default max_bin) the two
      matmul fusions were 90% of the device time and ran at 21% of the
      bf16 MXU peak, where HIGGS's `f32[16,63,120]{2,1,0}` runs at 93%
      (0.382 against 0.087 ms a chunk for 1,020 against 1,008 one-hot
      columns; TPU v5e, PR 38's ledger). Split, the same groups compile
      to `f32[16,64,120]{2,1,0}` and `f32[12,64,120]{2,1,0}`, one
      `u8[chunk, k*Gb]` fusion of shifted bins a block, and the
      histograms to the bit on the CPU; a block of at most 64 bins
      traces the program it traced before (PR 39, `PERF.md` section 6).
    """
    n, f = binned.shape
    if n % chunk != 0:
        raise ValueError(f"rows ({n}) must be padded to a multiple of chunk ({chunk})")

    def rows_of(c):
        w_chunk = jax.lax.dynamic_slice(weights, (c * chunk, 0), (chunk, 3))
        lid = jax.lax.dynamic_slice(leaf_id, (c * chunk,), (chunk,))
        return _chunk_blocks(binned, c, chunk), w_chunk, lid

    return _leaves_histogram(rows_of, ids, n // chunk, f, num_bins, chunk,
                             bf16, n_valid, group_widths, quantize)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "chunk", "bf16",
                                    "group_widths", "quantize"))
@scope("lgbm/hist/contract")
def gathered_leaves_histogram(binned: jnp.ndarray, weights: jnp.ndarray,
                              leaf_id: jnp.ndarray, rows: jnp.ndarray,
                              ids: jnp.ndarray, num_bins: int,
                              chunk: int = 16384, bf16: bool = True,
                              n_valid=None, group_widths=None,
                              quantize: str = "none") -> jnp.ndarray:
    """batched_leaves_histogram over a COMPACTED row subset.

    `rows` is a fixed-capacity [cap] i32 buffer of row indices into
    `binned` (cap a static multiple of `chunk`, so shapes stay
    compile-stable inside the grower's while_loop); only the first
    `n_valid` entries are real — the speculative grower packs the member
    rows of the selected expansion nodes with a cumsum-stable compaction
    (learner/grow.py) when those nodes jointly hold a small row
    fraction. Each chunk gathers its bin rows and weight channels
    through the index buffer and feeds the SAME one-hot contraction as
    batched_leaves_histogram, so the per-pass cost is O(rows-in-
    selected-nodes), not O(N) — the accelerator analogue of the
    reference's per-leaf index lists (data_partition.hpp:94-170), where
    histogram cost tracks the leaf, not the dataset.

    n_valid contract here differs from the full-pass kernels: buffer
    slots beyond n_valid alias row 0 (the compaction scatters real
    indices only), so the boundary chunk MASKS channels of dead slots to
    zero — the dynamic trip count then skips whole all-padding chunks
    for free, exactly like the padded-row suffix of the full pass.

    Returns [C, F, B, 3] like batched_leaves_histogram.
    """
    cap = rows.shape[0]
    f = binned.shape[1]
    if cap % chunk != 0:
        raise ValueError(
            f"row buffer ({cap}) must be a multiple of chunk ({chunk})")
    nv = jnp.int32(cap) if n_valid is None else \
        jnp.minimum(jnp.asarray(n_valid, jnp.int32), cap)

    def rows_of(c):
        r = jax.lax.dynamic_slice(rows, (c * chunk,), (chunk,))
        live = (c * chunk + jnp.arange(chunk, dtype=jnp.int32)) < nv
        def take(a):
            with scope("lgbm/hist/gather"):
                return a[r]

        w_chunk = jnp.where(live[:, None], take(weights), 0.0)
        b_rows = take(binned)                                  # [chunk, F]
        return (lambda gs, gc: jax.lax.slice_in_dim(
            b_rows, gs, gs + gc, axis=1)), w_chunk, take(leaf_id)

    return _leaves_histogram(rows_of, ids, cap // chunk, f, num_bins, chunk,
                             bf16, nv, group_widths, quantize)


def leaf_weights(grad: jnp.ndarray, hess: jnp.ndarray, leaf_id: jnp.ndarray,
                 leaf: jnp.ndarray, bag_weight: jnp.ndarray) -> jnp.ndarray:
    """Build the [N, 3] channel tensor selecting rows of `leaf`."""
    mask = (leaf_id == leaf)
    w = jnp.where(mask, bag_weight, 0.0)
    cnt = jnp.where(mask & (bag_weight > 0), 1.0, 0.0)
    return jnp.stack([grad * w, hess * w, cnt], axis=-1)
