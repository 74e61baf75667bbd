"""Dense-domain group-by: one Pallas kernel binning rows on the MXU.

The reference's ``BigintGroupByHash.java`` is the single-int-key fast
path of its hash aggregation; the TPU translation for a SMALL key domain
(G bins) avoids hashing entirely: every (bin, aggregate-limb) partial
sum is one cell of a matmul

    S[(tile, lane), l7] = sum_r  1[bin_hi(r)==tile] * limb_lane(r)
                                 * 1[bin_lo(r)==l7]
                        = (U @ V)[(tile, lane), l7]

with ``bin = bin_hi * 128 + bin_lo`` split across BOTH matmul dims so
M = T*LANES, K = B rows, N = 128 are all MXU-native (the naive one-hot
over all G bins wastes 127/128 of the array on the N dim).  Values are
decomposed into 8-bit limbs (exact in bfloat16; f32 accumulation stays
exact below 2^24 per bin per chunk, guaranteed by draining every
CH = 2^16 rows); the int32 drain pairs reconstruct exact sums of ANY
width on the host — including the 128-bit DECIMAL accumulators, via a
negative-count lane per signed column.

Not measured on the current chip.

The whole table streams through ONE gridless ``pallas_call``:
double-buffered HBM->VMEM DMA per chunk, accumulators resident in VMEM
for the whole table. The kernel DMAs its outputs to HBM itself, and its
consumer (``reconstruct_device``, or ``reconstruct`` on the host) runs in
a second jit, not fused with the producer.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# lane word codes (what an accumulator lane reads per row)
_W_ZERO = 60
_W_COUNT = 61
_W_SIGN_BASE = 100  # +ci: sign bit of column ci


@dataclasses.dataclass(frozen=True)
class DenseCol:
    """One int64-valued aggregate input column."""

    nonneg: bool       # True when column min >= 0 (skip high zero limbs)
    bits: int          # value bit-width needed (<= 64)

    @property
    def limbs(self) -> int:
        if not self.nonneg:
            return 8
        return max(1, (self.bits + 7) // 8)


@dataclasses.dataclass(frozen=True)
class DensePlan:
    """Static lane layout for one dense group-by program.

    ``pair128[ci]`` — the column's sums are consumed as exact 128-bit
    (hi, lo) accumulators (a ``sum128`` spec reads it), REGARDLESS of the
    data's sign; a negative-count lane is added only when the data can
    actually be negative (two's-complement bias correction)."""

    G: int             # padded bin count (multiple of 128)
    cols: tuple        # DenseCol per distinct input column
    pair128: tuple     # per column: emit exact 128-bit (hi, lo) sums

    def sign_lane(self, ci: int) -> bool:
        return self.pair128[ci] and not self.cols[ci].nonneg

    def lane_tables(self):
        """(word_code, shift_bytes) per accumulator lane."""
        codes, shifts = [], []
        for ci, col in enumerate(self.cols):
            for j in range(col.limbs):
                codes.append(2 * ci + (0 if j < 4 else 1))
                shifts.append((j % 4) * 8)
            if self.sign_lane(ci):
                codes.append(_W_SIGN_BASE + ci)
                shifts.append(0)
        codes.append(_W_COUNT)
        shifts.append(0)
        while len(codes) % 8:
            codes.append(_W_ZERO)
            shifts.append(0)
        return codes, shifts

    @property
    def lanes(self) -> int:
        return len(self.lane_tables()[0])

    @property
    def tiles(self) -> int:
        return self.G // 128

    @property
    def m(self) -> int:
        return self.tiles * self.lanes


def _make_kernel(plan: DensePlan, ncols: int, ncap: int, ch: int, b: int):
    T = plan.tiles
    LANES = plan.lanes
    M = plan.m
    G = plan.G
    nchunks = ncap // ch
    nsub = ch // b
    # f32 accumulator exactness: drain before any bin can exceed 2^24
    # (worst case all rows of an epoch in one bin x 255 per limb)
    drain_sub = max(1, min((1 << 16) // b, ch // b))
    nstreams = 1 + 2 * ncols  # bins + (lo, hi) per column

    def kernel(*refs):
        # inputs: code/shift lane tables + data streams
        ct_ref, st_ref = refs[0], refs[1]
        hbm = refs[2 : 2 + nstreams]
        hi_out, lo_out = refs[2 + nstreams], refs[3 + nstreams]
        bufs = refs[4 + nstreams : 4 + 2 * nstreams]
        accf, acchi, acclo = refs[4 + 2 * nstreams : 7 + 2 * nstreams]
        sems, outsem = refs[7 + 2 * nstreams], refs[8 + 2 * nstreams]
        acchi[:] = jnp.zeros_like(acchi)
        acclo[:] = jnp.zeros_like(acclo)

        def dma(c, slot):
            off = c * jnp.int32(ch)
            dst = pl.ds(slot * jnp.int32(ch), ch)
            return [
                pltpu.make_async_copy(
                    hbm[i].at[pl.ds(off, ch)], bufs[i].at[dst],
                    sems.at[slot, jnp.int32(i)],
                )
                for i in range(nstreams)
            ]

        for d in dma(jnp.int32(0), jnp.int32(0)):
            d.start()

        ct = ct_ref[:]
        st = st_ref[:]

        accf[:] = jnp.zeros_like(accf)

        def chunk_body(c, carry):
            slot = jax.lax.rem(c, jnp.int32(2))

            @pl.when(c + jnp.int32(1) < jnp.int32(nchunks))
            def _():
                for d in dma(c + jnp.int32(1), jnp.int32(1) - slot):
                    d.start()

            for d in dma(c, slot):
                d.wait()

            def body(s, _):
                off = slot * jnp.int32(ch) + s * jnp.int32(b)
                bins = bufs[0][pl.ds(off, b)]
                live = bins < G
                hi_t = jnp.where(live, bins >> jnp.int32(7), jnp.int32(T))
                lo7 = bins & jnp.int32(127)
                # u[(t, lane), r] built with 2-D ops only (3-D broadcast
                # relayouts are ~5x slower in Mosaic)
                word = jnp.zeros((M, b), jnp.int32)
                for ci in range(ncols):
                    vlo = bufs[1 + 2 * ci][pl.ds(off, b)]
                    vhi = bufs[2 + 2 * ci][pl.ds(off, b)]
                    word = jnp.where(ct == jnp.int32(2 * ci), vlo[None, :], word)
                    word = jnp.where(ct == jnp.int32(2 * ci + 1), vhi[None, :], word)
                    word = jnp.where(
                        ct == jnp.int32(_W_SIGN_BASE + ci),
                        ((vhi >> jnp.int32(31)) & jnp.int32(1))[None, :],
                        word,
                    )
                limbv = (word >> st) & jnp.int32(255)
                limbv = jnp.where(
                    ct == jnp.int32(_W_COUNT),
                    live[None, :].astype(jnp.int32),
                    jnp.where(ct == jnp.int32(_W_ZERO), jnp.int32(0), limbv),
                )
                m_iota = jax.lax.broadcasted_iota(jnp.int32, (M, b), 0)
                t_of_m = m_iota // jnp.int32(LANES)
                u = jnp.where(
                    t_of_m == hi_t[None, :], limbv, jnp.int32(0)
                ).astype(jnp.bfloat16)
                l_iota = jax.lax.broadcasted_iota(jnp.int32, (b, 128), 1)
                v = (l_iota == lo7[:, None]).astype(jnp.bfloat16)
                accf[:] = accf[:] + jnp.dot(
                    u, v, preferred_element_type=jnp.float32
                )
                return jnp.int32(0)

            def sub_epoch(e, _):
                jax.lax.fori_loop(
                    e * jnp.int32(drain_sub),
                    (e + jnp.int32(1)) * jnp.int32(drain_sub),
                    body, jnp.int32(0),
                )
                d32 = accf[:].astype(jnp.int32)
                acclo[:] = acclo[:] + (d32 & jnp.int32(0xFFFF))
                acchi[:] = acchi[:] + (d32 >> jnp.int32(16))
                accf[:] = jnp.zeros_like(accf)
                return jnp.int32(0)

            jax.lax.fori_loop(
                jnp.int32(0), jnp.int32(nsub // drain_sub), sub_epoch,
                jnp.int32(0),
            )
            return jnp.int32(0)

        jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(nchunks), chunk_body, jnp.int32(0)
        )
        d1 = pltpu.make_async_copy(acchi, hi_out, outsem.at[jnp.int32(0)])
        d2 = pltpu.make_async_copy(acclo, lo_out, outsem.at[jnp.int32(1)])
        d1.start()
        d2.start()
        d1.wait()
        d2.wait()

    return kernel


def dense_groupby_device(
    plan: DensePlan,
    bins: jnp.ndarray,
    value_cols: Sequence[jnp.ndarray],
    interpret: bool = False,
):
    """Run the binning kernel.  ``bins`` int32 (ncap,), values in [0, G]
    with G = dead row; ``value_cols`` int64 (ncap,) each.  ``ncap`` must
    be a power-of-two multiple of the chunk size.  Returns (hi, lo)
    int32 (M, 128) drain pairs for :func:`reconstruct`."""
    ncap = bins.shape[0]
    ncols = len(value_cols)
    ch = min(ncap, 1 << 18 if ncols <= 2 else 1 << 16)
    b = min(2048 if plan.m <= 512 else 1024, ch)
    streams = [bins.astype(jnp.int32)]
    for v in value_cols:
        u = v.astype(jnp.uint64)
        streams.append((u & jnp.uint64(0xFFFFFFFF)).astype(jnp.int32))
        streams.append((u >> jnp.uint64(32)).astype(jnp.int32))
    nstreams = len(streams)
    kernel = _make_kernel(plan, ncols, ncap, ch, b)
    M = plan.m
    codes, shifts = plan.lane_tables()
    code_m = jnp.asarray(np.tile(np.asarray(codes, np.int32), plan.tiles).reshape(M, 1))
    shift_m = jnp.asarray(np.tile(np.asarray(shifts, np.int32), plan.tiles).reshape(M, 1))
    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 2
        + [pl.BlockSpec(memory_space=pl.ANY)] * nstreams,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_shape=[
            jax.ShapeDtypeStruct((M, 128), jnp.int32),
            jax.ShapeDtypeStruct((M, 128), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((2 * ch,), jnp.int32)] * nstreams
        + [
            pltpu.VMEM((M, 128), jnp.float32),
            pltpu.VMEM((M, 128), jnp.int32),
            pltpu.VMEM((M, 128), jnp.int32),
            pltpu.SemaphoreType.DMA((2, nstreams)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(code_m, shift_m, *streams)


def reconstruct_device(plan: DensePlan, hi, lo, kmins, kstrides, kranges):
    """Device-side reconstruction, run in a SEPARATE jit from the pallas
    producer so the drained accumulators never leave the device.

    Returns (key_vals: list of (G,) int64 per key, col_sums: per column
    (G,) int64 modular sums or (G, 2) (hi, lo) 128-bit pairs, counts
    (G,) int64)."""
    T, LANES, G = plan.tiles, plan.lanes, plan.G
    lt = hi.astype(jnp.int64).reshape(T, LANES, 128) * 65536 + lo.astype(
        jnp.int64
    ).reshape(T, LANES, 128)
    lane = 0
    col_sums: list = []
    for ci, col in enumerate(plan.cols):
        if plan.pair128[ci]:
            from trino_tpu.ops.decimal128 import add128

            acc_hi = jnp.zeros(G, jnp.int64)
            acc_lo = jnp.zeros(G, jnp.int64)
            for j in range(col.limbs):
                c = lt[:, lane, :].reshape(G)  # < 2^48, non-negative
                sh = 8 * j
                c_lo = c << sh  # int64 wraps: the LOW 64 bits of c*2^sh
                if sh > 0:
                    c_hi = jax.lax.shift_right_logical(c, 64 - sh)
                else:
                    c_hi = jnp.zeros_like(c)
                acc_hi, acc_lo = add128(acc_hi, acc_lo, c_hi, c_lo)
                lane += 1
            if plan.sign_lane(ci):
                neg = lt[:, lane, :].reshape(G)
                lane += 1
                # two's-complement bias per negative row
                acc_hi = acc_hi - neg
            col_sums.append(jnp.stack([acc_hi, acc_lo], axis=1))
            continue
        acc = jnp.zeros(G, jnp.int64)
        for j in range(col.limbs):
            acc = acc + (lt[:, lane, :].reshape(G) << (8 * j))
            lane += 1
        col_sums.append(acc)
    counts = lt[:, lane, :].reshape(G)
    b = jnp.arange(G, dtype=jnp.int64)
    key_vals = [
        kmins[i] + (b // kstrides[i]) % kranges[i]
        for i in range(kmins.shape[0])
    ]
    return key_vals, col_sums, counts


def reconstruct(plan: DensePlan, hi, lo):
    """Host-side exact reconstruction: per bin, per column, the TRUE
    integer sum (python ints, any width) plus the group counts.

    Returns (sums: list per column of length-G list[int], counts:
    np.int64[G]).  In-graph consumption of pallas outputs is corrupted
    on this stack (see module docstring), and host math is exact and
    cheap at G <= 8192."""
    hi = np.asarray(hi).astype(np.int64)
    lo = np.asarray(lo).astype(np.int64)
    lt = hi * 65536 + lo                      # (M, 128) limb totals
    T, LANES, G = plan.tiles, plan.lanes, plan.G
    lt = lt.reshape(T, LANES, 128)
    lane = 0
    sums: list = []
    counts = None
    for ci, col in enumerate(plan.cols):
        ws = plan.sign_lane(ci)
        if plan.pair128[ci] and not col.nonneg:
            # exact signed sum of ANY width (128-bit DECIMAL
            # accumulators): python-int math over G bins only
            acc = np.zeros((T, 128), object)
            for j in range(col.limbs):
                acc = acc + lt[:, lane, :].astype(object) * (1 << (8 * j))
                lane += 1
            neg = lt[:, lane, :]
            lane += 1
            flat = acc.reshape(G) - neg.reshape(G).astype(object) * (1 << 64)
            sums.append([int(x) for x in flat])
            continue
        if plan.pair128[ci]:
            # nonneg pair128: exact big-int (no sign lane present)
            acc = np.zeros((T, 128), object)
            for j in range(col.limbs):
                acc = acc + lt[:, lane, :].astype(object) * (1 << (8 * j))
                lane += 1
            sums.append([int(x) for x in acc.reshape(G)])
            continue
        # modular int64 semantics: vectorized uint64 wrap (what plain
        # BIGINT sums need; for nonneg columns the result is exact)
        acc = np.zeros((T, 128), np.uint64)
        for j in range(col.limbs):
            acc = acc + (
                lt[:, lane, :].astype(np.uint64) << np.uint64(8 * j)
            )
            lane += 1
        sums.append(acc.reshape(G).view(np.int64).tolist())
    counts = lt[:, lane, :].reshape(G).astype(np.int64)
    return sums, counts
