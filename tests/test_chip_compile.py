"""The main path's kernels, compiled for the v5e at real sizes — no chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``v5e:2x2``). It refuses what interpret mode and
the CPU backend let through: a misaligned slice, too much VMEM, a scalar
store to VMEM, a program that does not fit HBM. Nothing runs, so these
cases say nothing about results or times; ``chip_smoke.py`` does that on
the chip. A kernel written for the chip by hand gets its case here
(``tests/test_lint.py`` holds the tree to that).

All cases stay in THIS file (one xdist worker then holds the TPU library),
and the topology is described inside a module fixture, never at import.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS, SingleDeviceSharding

from trino_tpu.ops import dense_join as DJ
from trino_tpu.ops import keypack
from trino_tpu.ops.join import lookup_join, probe_join
from trino_tpu.parallel.exchange import hash_repartition
from trino_tpu.parallel.mesh import AXIS

LINEITEM_SLAB = 1 << 23  # SF1 lineitem (6,001,215 rows) padded to a slab


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


_JOIN_BUILD, _JOIN_PROBE, _JOIN_CAP = 1 << 21, LINEITEM_SLAB, 1 << 23


def test_dense_join_build(one_chip):
    """The jnp build rounds at orders size: 2^21 build rows into a table
    at the engineered 4x load."""
    nb, cap = _JOIN_BUILD, _JOIN_CAP

    def build(bh, valid, sel):
        return DJ.build_table(DJ.slot_base_hash(bh, cap), valid, sel, cap)

    _compile(
        build,
        _shape(one_chip, (nb,), jnp.int64),
        _shape(one_chip, (nb,), jnp.bool_),
        _shape(one_chip, (nb,), jnp.bool_),
    )


def test_dense_join_probe(one_chip):
    """The jnp probe rounds at lineitem size: 2^23 probe rows against the
    2^21-row build's table."""
    nb, npr, cap = _JOIN_BUILD, _JOIN_PROBE, _JOIN_CAP

    def probe(table, bh, ph, pvalid, psel):
        return DJ.probe_table(
            table, bh, DJ.slot_base_hash(ph, cap), ph, pvalid, psel, npr
        )

    _compile(
        probe,
        _shape(one_chip, (cap,), jnp.int32),
        _shape(one_chip, (nb,), jnp.int64),
        _shape(one_chip, (npr,), jnp.int64),
        _shape(one_chip, (npr,), jnp.bool_),
        _shape(one_chip, (npr,), jnp.bool_),
    )


def test_sort_join_probe_has_no_loop(one_chip):
    """The sort tier's probe at Q3's lineitem join (6,001,215 probe rows,
    a 2^21-row build, 2^23 output slots): the chip's compiler must turn
    neither its sorts, its scans nor its scatter into a ``while``."""
    nb, npr, cap = _JOIN_BUILD, 6_001_215, _JOIN_CAP
    compiled = _compile(
        lambda sbk, sbi, cnt, ph, pv, psel: probe_join(
            sbk, sbi, cnt, ph, pv, psel, cap, "inner"
        ),
        _shape(one_chip, (nb,), jnp.int64),
        _shape(one_chip, (nb,), jnp.int32),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (npr,), jnp.int64),
        _shape(one_chip, (npr,), jnp.bool_),
        _shape(one_chip, (npr,), jnp.bool_),
    )
    assert "while" not in compiled.as_text()


def test_sort_join_lookup_has_no_loop(one_chip):
    """The sort tier's lookup at a step of Q5's slab loop (2,097,152 probe
    rows against the 2^21-row build of 1994's orders, whose key is unique):
    no ``while``, and no scatter, which only the expansion needs."""
    nb = npr = 1 << 21
    compiled = _compile(
        lambda sbk, sbi, cnt, ph, pv, psel: lookup_join(sbk, sbi, cnt, ph, pv, psel, "inner"),
        _shape(one_chip, (nb,), jnp.int64),
        _shape(one_chip, (nb,), jnp.int32),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (npr,), jnp.int64),
        _shape(one_chip, (npr,), jnp.bool_),
        _shape(one_chip, (npr,), jnp.bool_),
    )
    text = compiled.as_text()
    assert "while" not in text and " scatter(" not in text


@pytest.mark.parametrize("masks", [False, True], ids=["slab-step", "whole-batch"])
def test_domain_group_by_keeps_no_slot_by_row_array(one_chip, masks):
    """``group_aggregate``'s domain path at Q1's lanes: a slab step's
    2,097,152 rows and 12 slots, and the default session's whole batch of
    2^23 rows with validity masks on both keys, 20 slots. Compare, select
    and reduce have to fuse: a (slots, rows) int64 array is 200 MB to 1.3 GB
    a lane. And nothing is sorted."""
    from trino_tpu.ops.aggregation import AggSpec, domain_slots, group_aggregate

    n = LINEITEM_SLAB if masks else 1 << 21
    specs = [AggSpec(k) for k in ("sum128", "sum128", "sum128w", "sum128w",
                                  "sum128", "sum128", "sum128", "count_star")]

    def q1(flag, status, fvalid, svalid, sel, qty, price, disc_price, charge, disc):
        keys = [(flag, fvalid if masks else None),
                (status, svalid if masks else None)]
        lanes = [(qty, None), (price, None), (disc_price, None), (charge, None),
                 (qty, None), (price, None), (disc, None), None]
        assert domain_slots(keys, lanes, specs, 4096, [3, 2]) == (20 if masks else 12)
        return group_aggregate(keys, sel, lanes, specs, 4096, key_domains=[3, 2])

    compiled = _compile(
        q1,
        _shape(one_chip, (n,), jnp.int32), _shape(one_chip, (n,), jnp.int32),
        _shape(one_chip, (n,), jnp.bool_), _shape(one_chip, (n,), jnp.bool_),
        _shape(one_chip, (n,), jnp.bool_),
        _shape(one_chip, (n,), jnp.int64), _shape(one_chip, (n,), jnp.int64),
        _shape(one_chip, (n, 2), jnp.int64), _shape(one_chip, (n, 2), jnp.int64),
        _shape(one_chip, (n,), jnp.int64),
    )
    # the limb lanes may be kept (4 B a row each); a slot-by-row array may not
    assert compiled.memory_analysis().temp_size_in_bytes < 12 * n * 4
    text = compiled.as_text()
    assert " sort(" not in text and " while(" not in text


# sel bit + key bits + 23 row-index bits, packed into 63-bit int64 lanes.
# The compile time is the comparator's, not the row count's: one lane takes
# ~20 s here and three take ~150 s, so the wide case is slow-marked.
_SORT_CASES = {
    "1-lane": (jnp.int32,),
    "3-lanes": (jnp.int64, jnp.int64, jnp.int32),
}


@pytest.mark.parametrize(
    "case",
    ["1-lane", pytest.param("3-lanes", marks=pytest.mark.slow)],
)
def test_keypack_sort(one_chip, case):
    """The packed grouping sort at 2^23 rows."""
    n = LINEITEM_SLAB
    dtypes = _SORT_CASES[case]

    def sort(sel, *cols):
        keys = [(c, None) for c in cols]
        lanes = keypack.KeyPlan(keys, sel_present=True).num_lanes
        assert lanes == len(dtypes), lanes
        return keypack.grouping_sort(keys, sel, n)

    _compile(
        sort,
        _shape(one_chip, (n,), jnp.bool_),
        *[_shape(one_chip, (n,), dt) for dt in dtypes],
    )


@pytest.mark.parametrize("rows", [1 << 23, 1 << 24])
def test_an_int64_prefix_sum_inside_a_loop_at_a_wide_slab_steps_rows(one_chip, rows):
    """``_SortedSegments.sum``'s prefix sum inside the slab loop, at the rows
    of a step widened from a million-group budget (``slab_step_rows``). With
    the block totals scanned in one window the compiler refuses both sizes
    inside a loop (scoped vmem 64.23M and 19.09M over 16.00M, 25-50 s each to
    say so); ``_blocked_scan`` sends them through itself past
    ``_SCAN_DIRECT_BLOCKS``."""
    from trino_tpu.ops.aggregation import _SCAN_DIRECT_BLOCKS, _prefix_sum

    assert rows // 512 > _SCAN_DIRECT_BLOCKS == (1 << 21) // 512

    def loop(x, steps):
        def body(i, acc):
            return acc + _prefix_sum(x + i.astype(jnp.int64))

        return jax.lax.fori_loop(0, steps, body, jnp.zeros_like(x))

    _compile(loop, _shape(one_chip, (rows,), jnp.int64), _shape(one_chip, (), jnp.int32))


def test_repartition_is_an_all_to_all_on_four_chips(topo):
    """The repartition shuffle over a 4-device mesh of the described
    chips: the compiler must put an all-to-all in, on every device."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), (AXIS,))
    rows = NamedSharding(mesh, PS(AXIS))
    n, bucket = LINEITEM_SLAB, 1 << 20

    def shuffle(a, b, khash, sel):
        return hash_repartition(mesh, [a, b], khash, sel, bucket)

    compiled = _compile(
        shuffle,
        _shape(rows, (n,), jnp.int64),
        _shape(rows, (n,), jnp.int64),
        _shape(rows, (n,), jnp.int64),
        _shape(rows, (n,), jnp.bool_),
    )
    assert "all-to-all" in compiled.as_text()


def test_a_shards_step_is_sliced_where_it_lies_on_four_chips(topo):
    """The mesh's slab loop (``StreamingAggregator._shard_chunk`` inside a
    ``fori_loop``) at SF1's shapes: lineitem's four columns row-sharded, each
    shard padded to 4,194,304 rows, a step of 2,097,152 rows a shard. Every
    device slices its own rows: no collective moves a row of the table."""
    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.exec.streaming import StreamingAggregator

    mesh = Mesh(np.asarray(topo.devices).reshape(4), (AXIS,))
    rows, held, cap = NamedSharding(mesh, PS(AXIS)), 1 << 22, 1 << 21
    sagg = StreamingAggregator.__new__(StreamingAggregator)
    sagg.mesh = mesh

    def loop(columns, valid, steps):
        slab = Batch(
            [Column(T.BIGINT, c, valid if j == 0 else None) for j, c in enumerate(columns)],
            4 * held,
        )

        def body(i, acc):
            chunk = sagg._shard_chunk(slab, i.astype(jnp.int64) * cap, cap)
            live = chunk[0].valid
            return acc + sum(jnp.where(live, c.data, 0) for c in chunk)

        return jax.lax.fori_loop(0, steps, body, jnp.zeros(4 * cap, jnp.int64))

    compiled = _compile(
        loop,
        [_shape(rows, (4 * held,), jnp.int64) for _ in range(4)],
        _shape(rows, (4 * held,), jnp.bool_),
        _shape(NamedSharding(mesh, PS()), (), jnp.int32),
    )
    text = compiled.as_text()
    assert "dynamic-slice" in text
    assert not [op for op in ("all-gather", "all-to-all", "collective-permute") if op in text]
