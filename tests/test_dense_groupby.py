"""ops/dense_groupby tests: the Pallas MXU binning kernel.

The real kernel needs the TPU Mosaic backend; the CPU mesh exercises
the kernel logic through pallas interpret mode at small sizes and the
plan/reconstruction algebra directly.  Run on a chip, the same tests
compile the native kernel; tests/test_chip_compile.py compiles it for the
v5e at real sizes without one.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops.dense_groupby import (
    DenseCol,
    DensePlan,
    dense_groupby_device,
    reconstruct,
    reconstruct_device,
)

def _on_tpu() -> bool:
    # asked inside a test, never while the module is imported
    return jax.default_backend() == "tpu"


def _run(plan, bins, vals):
    return dense_groupby_device(plan, bins, vals, interpret=not _on_tpu())


class TestDenseKernel:
    def test_sum_count_exact(self):
        G = 256
        n = 1 << 15
        rng = np.random.default_rng(0)
        keys = rng.integers(0, 250, n)
        vals = rng.integers(0, 1 << 20, n)
        sel = rng.random(n) < 0.8
        plan = DensePlan(
            G=G, cols=(DenseCol(nonneg=True, bits=20),), pair128=(False,)
        )
        bins = jnp.asarray(np.where(sel, keys, G), jnp.int32)
        hi, lo = jax.jit(lambda b, v: _run(plan, b, [v]))(
            bins, jnp.asarray(vals, jnp.int64)
        )
        sums, counts = reconstruct(plan, hi, lo)
        want_c = np.bincount(np.where(sel, keys, G), minlength=G + 1)[:G]
        assert np.array_equal(counts, want_c)
        want_s = np.zeros(G, np.int64)
        np.add.at(want_s, keys[sel], vals[sel])
        assert sums[0] == want_s.tolist()

    def test_signed_128bit_sums(self):
        G = 128
        n = 1 << 15
        rng = np.random.default_rng(1)
        keys = rng.integers(0, G, n)
        vals = rng.integers(-(1 << 55), 1 << 55, n)
        plan = DensePlan(
            G=G, cols=(DenseCol(nonneg=False, bits=64),), pair128=(True,)
        )
        bins = jnp.asarray(keys, jnp.int32)
        hi, lo = jax.jit(lambda b, v: _run(plan, b, [v]))(
            bins, jnp.asarray(vals, jnp.int64)
        )
        sums, counts = reconstruct(plan, hi, lo)
        want = [0] * G
        for k, v in zip(keys, vals):
            want[k] += int(v)
        assert sums[0] == want  # exact python-int equality, any width
        assert np.array_equal(counts, np.bincount(keys, minlength=G))

    def test_nonneg_pair128_exceeds_int64(self):
        """sum128 over NON-NEGATIVE data must still get exact 128-bit
        pairs (the review-flagged wire-format bug: the pair is keyed to
        the consuming spec, not the data's sign)."""
        G = 128
        n = 1 << 14
        rng = np.random.default_rng(9)
        keys = rng.integers(0, 4, n)  # few groups -> huge per-group sums
        vals = rng.integers((1 << 62) - 1000, (1 << 62), n)
        plan = DensePlan(
            G=G, cols=(DenseCol(nonneg=True, bits=62),), pair128=(True,)
        )
        bins = jnp.asarray(keys, jnp.int32)
        hi, lo = jax.jit(lambda b, v: _run(plan, b, [v]))(
            bins, jnp.asarray(vals, jnp.int64)
        )
        sums, counts = reconstruct(plan, hi, lo)
        want = [0] * G
        for k, v in zip(keys, vals):
            want[k] += int(v)
        assert sums[0] == want  # sums far beyond 2^64: no modular wrap
        # device pair recon agrees
        kv, sums_d, counts_d = jax.jit(
            lambda h, l: reconstruct_device(
                plan, h, l,
                jnp.asarray([0], jnp.int64),
                jnp.asarray([1], jnp.int64),
                jnp.asarray([G], jnp.int64),
            )
        )(hi, lo)
        pair = np.asarray(sums_d[0])
        for g in range(G):
            got = (int(pair[g, 0]) << 64) + (int(pair[g, 1]) & ((1 << 64) - 1))
            assert got == want[g], g

    def test_device_reconstruction_matches_host(self):
        G = 256
        n = 1 << 15
        rng = np.random.default_rng(2)
        keys = rng.integers(0, G, n)
        v1 = rng.integers(0, 1 << 30, n)
        v2 = rng.integers(-(1 << 40), 1 << 40, n)
        plan = DensePlan(
            G=G,
            cols=(DenseCol(True, 30), DenseCol(False, 64)),
            pair128=(False, True),
        )
        bins = jnp.asarray(keys, jnp.int32)
        hi, lo = jax.jit(lambda b, a, c: _run(plan, b, [a, c]))(
            bins, jnp.asarray(v1, jnp.int64), jnp.asarray(v2, jnp.int64)
        )
        sums_h, counts_h = reconstruct(plan, hi, lo)
        kv, sums_d, counts_d = jax.jit(
            lambda h, l: reconstruct_device(
                plan, h, l,
                jnp.asarray([0], jnp.int64),
                jnp.asarray([1], jnp.int64),
                jnp.asarray([G], jnp.int64),
            )
        )(hi, lo)
        assert np.array_equal(np.asarray(counts_d), counts_h)
        assert np.asarray(sums_d[0]).tolist() == sums_h[0]
        # signed column: device pair (hi, lo) must equal the exact sum
        pair = np.asarray(sums_d[1])
        for g in range(G):
            got = (int(pair[g, 0]) << 64) + (int(pair[g, 1]) & ((1 << 64) - 1))
            assert got == sums_h[1][g], g
        assert np.array_equal(np.asarray(kv[0]), np.arange(G))


class TestEngineDensePath:
    def test_sql_group_by_through_dense(self):
        if not _on_tpu():
            pytest.skip("engine dense path is TPU-only")
        from trino_tpu import types as T
        from trino_tpu.columnar import Batch, Column
        from trino_tpu.connectors.api import ColumnSchema, TableSchema
        from trino_tpu.testing import LocalQueryRunner

        n = 1 << 16
        runner = LocalQueryRunner()
        runner.session.set("execution_mode", "distributed")
        runner.session.set("stream_scan_threshold_rows", 1 << 14)
        rng = np.random.default_rng(7)
        keys = rng.integers(0, 97, n).astype(np.int64)
        vals = rng.integers(-(1 << 30), 1 << 30, n).astype(np.int64)
        mem = runner.catalogs.get("memory")
        mem.create_table(
            "default", "dense_t",
            TableSchema("dense_t", (ColumnSchema("k", T.BIGINT),
                                    ColumnSchema("v", T.BIGINT))),
        )
        mem.insert("default", "dense_t",
                   Batch([Column(T.BIGINT, keys), Column(T.BIGINT, vals)], n))
        rows, _ = runner.execute(
            "select k, sum(v), count(*) from memory.default.dense_t group by k"
        )
        want_s = np.zeros(97, np.int64)
        np.add.at(want_s, keys, vals)
        want_c = np.bincount(keys, minlength=97)
        got = {int(r[0]): (int(r[1]), int(r[2])) for r in rows}
        assert got == {
            k: (int(want_s[k]), int(want_c[k])) for k in range(97)
        }
