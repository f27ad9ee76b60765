"""Pallas dense group-by reduction kernels (MXU one-hot matmul).

XLA's scatter-add lowers colliding updates catastrophically on TPU
(~11M rows/s measured for 16M rows into 100 slots); these kernels
replace it for the dense-domain aggregate path — the role Tungsten's
`UnsafeFixedWidthAggregationMap.java:39`/`BytesToBytesMap.java` hash loop
plays on CPU in the reference.

Small domains (<= 512 columns): per-group sums are `limbs @ onehot(idx)`
with the one-hot tile living only in VMEM ([T, D] bf16) and the
contraction on the MXU.

Large domains (up to ~2^20): building a [T, D] one-hot costs D VPU ops
per ROW — the round-3 profiling showed that construction, not the
matmul, capped the 65,536-group benchmark at ~2M rows/s. The factorized
kernel instead decomposes idx = a*dB + b and uses
``onehot_D(idx) = onehot_dA(a) (x) onehot_dB(b)``:
``G[a, b] = sum_t (A[t, a] * limb[t]) * B[t, b]`` — an [dA, T] @ [T, dB]
MXU contraction per limb row whose one-hot build cost is dA+dB (~512)
instead of D (~65,536) comparisons per row.

Exactness: int64 contributions are split into 8-bit limbs (exact in
bf16) over uint32 halves; a super-tile accumulates S*T rows with
per-limb partials <= S*T*255 < 2^24, exact in the f32 MXU accumulator;
super-tile partials are summed in int64 and limb sums recombined mod
2^64 — bit-exact int64 arithmetic at MXU speed. Rows whose values are
statically bounded (counts: AccSpec.width) carry only the limbs their
width needs — the bench shape's [count, sum, sum_cnt] needs 10 limb
rows instead of 24. float64 contributions ride as Kahan-compensated
(hi, lo) float32 pairs on the VPU (small domains; large float domains
fall back to scatter in the caller).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_I0 = np.int32(0)    # index-map constants must be int32 for Mosaic
TILE = 8192          # rows per grid step (large: amortizes per-step
                     # overhead — 1024-row tiles left the MXU at ~10%
                     # on the 65k-domain shape, round-4 profiling)
SUPER = 8            # tiles per exact-f32 accumulation window
D_BLOCK = 512        # small-domain kernel: columns per block
FACTOR_B = 512       # factorized kernel: dB (lane dimension)
PARTIAL_BUDGET = 256 * 1024 * 1024  # max bytes of per-call partial sums
#: scoped-VMEM ceiling handed to Mosaic with every call, and the budget
#: the block sizes below are fitted to. The compiler's 16 MiB default
#: refuses a TILE-row one-hot beside a double-buffered output slab
#: ("Scoped allocation with size 24.75M and limit 16.00M" for 9 limb
#: rows at a_blk=128); blocks that fit 16 MiB would shrink a_blk to a
#: few sublanes. A v5e core has 128 MiB of VMEM.
VMEM_LIMIT = 64 * 1024 * 1024

assert TILE * SUPER * 255 < (1 << 25)  # f32-exact window


def _factored_vmem_bytes(a_blk: int, d_b: int, n_words: int,
                         n_limb_rows: int) -> int:
    """Upper bound on one grid step of `_factored_kernel`: pipelined
    blocks count twice (double buffering), and each one-hot is charged
    its 32-bit compare next to its bf16 form. Measured against the v5e
    compiler (smallest limit that compiles) it overstates by 1.5-3.5x;
    the compiler decides live ranges, so the bound has to be loose."""
    ins = 2 * (2 + n_words) * TILE * 4
    out = 2 * n_limb_rows * a_blk * d_b * 4
    onehot_b = TILE * d_b * (4 + 2)
    onehot_a = TILE * a_blk * (4 + 2 + 2)  # compare, one-hot, scaled
    return ins + out + onehot_b + onehot_a + a_blk * d_b * 4


def _small_vmem_bytes(d_block: int, n_words: int, n_limb_rows: int,
                      n_float_rows: int) -> int:
    """Upper bound on one grid step of `_small_kernel`, same rules as
    `_factored_vmem_bytes`; the float path holds the [T, D] match mask
    and ONE masked row at a time (rows accumulate in sequence)."""
    ins = 2 * (1 + n_words + n_float_rows) * TILE * 4
    out = 2 * (n_limb_rows + 2 * n_float_rows) * d_block * 4
    ints = (TILE * d_block * (4 + 2)
            + n_limb_rows * TILE * (4 + 4 + 2)) if n_limb_rows else 0
    floats = TILE * d_block * (4 + 4) if n_float_rows else 0
    return ins + out + ints + floats


def _fit_block(cap: int, step: int, vmem_bytes) -> int:
    """Largest multiple of `step` <= cap whose grid step fits
    VMEM_LIMIT; a shape whose smallest block does not fit is refused
    here, by name, before the compiler sees it."""
    blk = max(step, (cap // step) * step)
    while blk > step and vmem_bytes(blk) > VMEM_LIMIT:
        blk -= step
    if vmem_bytes(blk) > VMEM_LIMIT:
        raise ValueError(
            f"pallas dense_groupby: a {step}-wide block needs "
            f"{vmem_bytes(blk)} bytes of VMEM, over the "
            f"{VMEM_LIMIT}-byte limit (too many aggregate rows for "
            f"the MXU kernel)")
    return blk


def _limb_layout(widths: Sequence[int]) -> List[Tuple[int, int, int]]:
    """Static limb plan: (int_row, half, shift8) triples. `half` selects
    the lo (0) or hi (1) uint32 word; shift8 the byte within it. Rows
    with width w <= promise values in [0, 2^w)."""
    layout = []
    for k, w in enumerate(widths):
        n_limbs = max(1, -(-min(w, 64) // 8))
        for limb in range(n_limbs):
            half, shift8 = divmod(limb, 4)
            layout.append((k, half, shift8))
    return layout


def _split_u32(int_rows: List, widths: Sequence[int], pad_rows) -> Tuple:
    """Stack the uint32 words the layout needs: all lo words, then hi
    words for rows wider than 32 bits. Returns (u32 [W, N], word_index
    map {(row, half) -> u32 row}).

    Rows with width <= 32 skip the int64 round trip entirely (a direct
    int32 truncation is exact for them): int64 is software-emulated on
    TPU and these passes showed up at chunk scale in round-4 profiles."""
    words = []
    index = {}
    for k, r in enumerate(int_rows):
        index[(k, 0)] = len(words)
        if widths[k] <= 32:
            words.append(pad_rows(r).astype(jnp.int32))
            continue
        iv = pad_rows(r.astype(jnp.int64))
        words.append((iv & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
                     .view(jnp.int32))
        index[(k, 1)] = len(words)
        words.append((iv >> 32).astype(jnp.int32))
    return jnp.stack(words), index


def _small_kernel(*refs, n_words: int, limb_plan, n_float_rows: int,
                  d_block: int):
    """One-hot [T, D] formulation for domains <= D_BLOCK."""
    pos = 0
    idx_ref = refs[pos]; pos += 1
    words_ref = None
    floats_ref = None
    if limb_plan:
        words_ref = refs[pos]; pos += 1
    if n_float_rows:
        floats_ref = refs[pos]; pos += 1
    iout_ref = None
    fout_ref = None
    if limb_plan:
        iout_ref = refs[pos]; pos += 1
    if n_float_rows:
        fout_ref = refs[pos]; pos += 1

    t = pl.program_id(2)
    d = pl.program_id(1)
    idx = idx_ref[:]  # [T] int32; out-of-range rows never match
    col = (jax.lax.broadcasted_iota(jnp.int32, (TILE, d_block), 1)
           + d * d_block)

    if limb_plan:
        onehot_b = (idx[:, None] == col).astype(jnp.bfloat16)
        w = words_ref[:, :]  # [W, T] int32 words
        # arithmetic shift + mask extracts unsigned limbs exactly
        limbs = jnp.concatenate(
            [((w[word] >> (8 * s)) & jnp.int32(0xFF))
             .astype(jnp.float32).astype(jnp.bfloat16)[None, :]
             for (word, s) in limb_plan], axis=0)  # [R, T]
        ipart = jax.lax.dot_general(
            limbs, onehot_b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(t == 0)
        def _():
            iout_ref[0] = ipart

        @pl.when(t > 0)
        def _():
            iout_ref[0] += ipart

    if n_float_rows:
        # floats avoid the MXU (f32 matmul decomposes into lossy bf16
        # passes): VPU masked reduce keeps true f32 adds, Kahan across t
        match = idx[:, None] == col  # [T, DB] bool
        # one row at a time, each folded into the output block before
        # the next is built: stacking the rows first kept every
        # [T, DB] f32 masked temporary alive at once (4 MiB a row)
        for r in range(n_float_rows):
            v = floats_ref[r, :]  # [T] f32
            fpart = jnp.sum(jnp.where(match, v[:, None], 0.0), axis=0,
                            keepdims=True)  # [1, DB] f32
            sum_r = slice(r, r + 1)
            comp_r = slice(n_float_rows + r, n_float_rows + r + 1)

            @pl.when(t == 0)
            def _():
                fout_ref[0, sum_r] = fpart
                fout_ref[0, comp_r] = jnp.zeros_like(fpart)

            @pl.when(t > 0)
            def _():
                s = fout_ref[0, sum_r]
                c = fout_ref[0, comp_r]
                y = fpart - c
                tt = s + y
                fout_ref[0, comp_r] = (tt - s) - y
                fout_ref[0, sum_r] = tt


def _factored_kernel(ia_ref, ib_ref, words_ref, out_ref, *,
                     limb_plan, a_blk: int, d_b: int):
    """Kronecker-factorized one-hot for large domains: per limb row r,
    G_r[a, b] += sum_t (A[t, a] * limb_r[t]) * B[t, b] on the MXU.
    The a-axis is gridded in `a_blk` blocks to bound the VMEM-resident
    output slab (R * a_blk * d_b f32)."""
    a = pl.program_id(1)
    t = pl.program_id(2)
    ia = ia_ref[:]  # [T] int32 in [0, d_a) (out-of-range rows match none)
    ib = ib_ref[:]
    rows_a = (jax.lax.broadcasted_iota(jnp.int32, (TILE, a_blk), 1)
              + a * a_blk)
    rows_b = jax.lax.broadcasted_iota(jnp.int32, (TILE, d_b), 1)
    onehot_a = (ia[:, None] == rows_a).astype(jnp.bfloat16)  # [T, aB]
    onehot_b = (ib[:, None] == rows_b).astype(jnp.bfloat16)  # [T, dB]
    w = words_ref[:, :]

    @pl.when(t == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    # each limb's [aB, dB] product goes straight into its output row:
    # concatenating all R products first held a second output slab
    for r, (word, s) in enumerate(limb_plan):
        # minor-dim insertion must happen on the 32-bit value (Mosaic
        # rejects it on bf16); cast after the [T] -> [T, 1] reshape
        limb2 = ((w[word][:, None] >> (8 * s)) & jnp.int32(0xFF)) \
            .astype(jnp.float32).astype(jnp.bfloat16)  # [T, 1]
        scaled_a = onehot_a * limb2                     # [T, aB]
        out_ref[0, r] += jax.lax.dot_general(
            scaled_a, onehot_b, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [aB, dB]


def dense_groupby_sums(idx, int_rows: Sequence, float_rows: Sequence,
                       domain: int, interpret: bool = False,
                       int_widths: Optional[Sequence[int]] = None
                       ) -> Tuple[List, List]:
    """Exact per-group sums.

    idx: int32[N] in [0, domain) (out-of-range rows are dropped);
    int_rows: int64[N] contribution arrays (int_widths[k] bounds row k's
    values to [0, 2^w) — fewer limbs); float_rows: float64[N].
    Returns ([int64[domain]], [float64[domain]]).
    """
    n = idx.shape[0]
    n_i = len(int_rows)
    n_f = len(float_rows)
    widths = list(int_widths) if int_widths is not None else [64] * n_i
    assert len(widths) == n_i
    rows_per_super = TILE * SUPER
    num_super = max(1, -(-n // rows_per_super))
    n_pad = num_super * rows_per_super

    use_factored = domain > D_BLOCK and n_i > 0
    if use_factored and n_f:
        raise ValueError("float rows unsupported for large domains "
                         "(caller must fall back to scatter)")

    layout = _limb_layout(widths)
    n_limb_rows = len(layout)
    n_words = sum(1 if w <= 32 else 2 for w in widths)  # _split_u32
    n_float_rows = 2 * n_f

    if use_factored:
        d_b = FACTOR_B
        d_a = -(-domain // d_b)
        d_a = -(-d_a // 8) * 8  # sublane multiple
        d_pad = d_a * d_b
        a_max = _fit_block(d_a, 8, lambda a: _factored_vmem_bytes(
            a, d_b, n_words, n_limb_rows))
        # even split of the a-axis: no block of pure padding
        num_ablk = -(-d_a // a_max)
        a_blk = -(-d_a // (8 * num_ablk)) * 8
    else:
        d_pad = -(-domain // 128) * 128
        d_block = _fit_block(min(D_BLOCK, d_pad), 128,
                             lambda d: _small_vmem_bytes(
                                 d, n_words, n_limb_rows, n_float_rows))
        num_dblk = -(-d_pad // d_block)
        d_pad = num_dblk * d_block
    params = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)

    idx32 = idx.astype(jnp.int32)
    if n_pad != n:
        # padding rows get an index that matches no one-hot column
        idx32 = jnp.pad(idx32, (0, n_pad - n), constant_values=d_pad)

    def pad_rows(r):
        return jnp.pad(r, (0, n_pad - n)) if n_pad != n else r

    u32 = word_index = None
    if n_i:
        u32, word_index = _split_u32(int_rows, widths, pad_rows)
        assert u32.shape[0] == n_words
    limb_plan = tuple((word_index[(k, h)], s) for (k, h, s) in layout) \
        if n_i else ()

    f32 = None
    if n_f:
        fv = jnp.stack([pad_rows(r.astype(jnp.float64)) for r in float_rows])
        fhi = fv.astype(jnp.float32)
        flo = (fv - fhi.astype(jnp.float64)).astype(jnp.float32)
        f32 = jnp.concatenate([fhi, flo], axis=0)  # [2*n_f, Npad]

    # per-super partial buffers scale as num_super * rows * d_pad f32;
    # chunk supers so one call's partials fit PARTIAL_BUDGET (floats
    # carry 2x rows: Kahan sums + compensations)
    bytes_per_super = (n_limb_rows + 2 * n_float_rows) * d_pad * 4
    supers_per_call = max(1, min(num_super,
                                 PARTIAL_BUDGET // max(1, bytes_per_super)))

    limb_acc = None   # [R, d_pad] int64
    float_acc = None  # [2*n_f, d_pad] f64
    start = 0
    while start < num_super:
        cs = min(supers_per_call, num_super - start)
        r0 = start * rows_per_super
        r1 = (start + cs) * rows_per_super
        idx_c = jax.lax.slice_in_dim(idx32, r0, r1)

        if use_factored:
            ia = jnp.minimum(idx_c // d_b, d_a)  # padding -> row d_a: none
            ib = idx_c % d_b
            u32_c = jax.lax.slice_in_dim(u32, r0, r1, axis=1)
            out = pl.pallas_call(
                functools.partial(_factored_kernel, limb_plan=limb_plan,
                                  a_blk=a_blk, d_b=d_b),
                grid=(cs, num_ablk, SUPER),
                in_specs=[
                    pl.BlockSpec((TILE,), lambda s, a, t: (s * SUPER + t,),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((TILE,), lambda s, a, t: (s * SUPER + t,),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((n_words, TILE),
                                 lambda s, a, t: (_I0, s * SUPER + t),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec(
                    (1, n_limb_rows, a_blk, d_b),
                    lambda s, a, t: (s, _I0, a, _I0),
                    memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct(
                    (cs, n_limb_rows, num_ablk * a_blk, d_b), jnp.float32),
                compiler_params=params,
                name="dense_groupby_factored",
                interpret=interpret,
            )(ia, ib, u32_c)
            part = out.astype(jnp.int64).sum(axis=0) \
                .reshape(n_limb_rows, num_ablk * a_blk * d_b)[:, :d_pad]
            limb_acc = part if limb_acc is None else limb_acc + part
        else:
            operands = [idx_c]
            in_specs = [pl.BlockSpec((TILE,),
                                     lambda s, d, t: (s * SUPER + t,),
                                     memory_space=pltpu.VMEM)]
            out_shapes = []
            out_specs = []
            if n_i:
                operands.append(jax.lax.slice_in_dim(u32, r0, r1, axis=1))
                in_specs.append(pl.BlockSpec(
                    (n_words, TILE), lambda s, d, t: (_I0, s * SUPER + t),
                    memory_space=pltpu.VMEM))
                out_shapes.append(jax.ShapeDtypeStruct(
                    (cs, n_limb_rows, d_pad), jnp.float32))
                out_specs.append(pl.BlockSpec(
                    (1, n_limb_rows, d_block), lambda s, d, t: (s, _I0, d),
                    memory_space=pltpu.VMEM))
            if n_f:
                operands.append(jax.lax.slice_in_dim(f32, r0, r1, axis=1))
                in_specs.append(pl.BlockSpec(
                    (n_float_rows, TILE),
                    lambda s, d, t: (_I0, s * SUPER + t),
                    memory_space=pltpu.VMEM))
                # 2x rows: [0:RF] Kahan sums, [RF:2RF] compensations
                out_shapes.append(jax.ShapeDtypeStruct(
                    (cs, 2 * n_float_rows, d_pad), jnp.float32))
                out_specs.append(pl.BlockSpec(
                    (1, 2 * n_float_rows, d_block),
                    lambda s, d, t: (s, _I0, d),
                    memory_space=pltpu.VMEM))

            outs = pl.pallas_call(
                functools.partial(_small_kernel, n_words=n_words,
                                  limb_plan=limb_plan,
                                  n_float_rows=n_float_rows,
                                  d_block=d_block),
                grid=(cs, num_dblk, SUPER),
                in_specs=in_specs,
                out_specs=out_specs,
                out_shape=out_shapes,
                compiler_params=params,
                name="dense_groupby_small",
                interpret=interpret,
            )(*operands)
            pos = 0
            if n_i:
                part = outs[pos].astype(jnp.int64).sum(axis=0)
                limb_acc = part if limb_acc is None else limb_acc + part
                pos += 1
            if n_f:
                fpart = outs[pos]
                sums = fpart[:, :n_float_rows].astype(jnp.float64)
                comps = fpart[:, n_float_rows:].astype(jnp.float64)
                part = (sums - comps).sum(axis=0)
                float_acc = part if float_acc is None else float_acc + part
        start += cs

    int_out: List = []
    if n_i:
        # exact int64 limb recombination per the static layout
        totals = [jnp.zeros((d_pad,), jnp.int64) for _ in range(n_i)]
        for r, (k, half, s) in enumerate(layout):
            totals[k] = totals[k] + (limb_acc[r] << (8 * s + 32 * half))
        int_out = [t[:domain] for t in totals]
    float_out: List = []
    if n_f:
        for k in range(n_f):
            float_out.append((float_acc[k] + float_acc[n_f + k])[:domain])
    return int_out, float_out
