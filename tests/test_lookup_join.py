"""A join whose build key is unique, run as a lookup (``ops/join.py::lookup_join``
through ``parallel/distributed.py::_sharded_probe(lookup=True)``): output row
``i`` is probe row ``i``, no probe column is gathered, and the selected
(probe row, build row) pairs are those of the expansion (``probe_join`` and
``verify_equal``) on the same build. On a one-device and a four-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops import join as J
from trino_tpu.parallel.distributed import _sharded_probe
from trino_tpu.parallel.mesh import make_mesh

#: probe rows (a multiple of four devices); build rows
N_PROBE, N_BUILD = 64, 24


def _sides(case, seed=7):
    """Both sides as ``_sharded_probe`` takes them: each side's row ids as its
    one payload column, its key lanes ``[(data, valid), ...]`` and its
    selection. The build's keys are distinct among its live rows."""
    rng = np.random.default_rng(seed)
    n_build = 0 if case == "empty build" else N_BUILD
    width = 2 if case == "two-column key" else 1
    # distinct build keys (in the wide case its first lane alone repeats)
    if width == 1:
        bkeys = [rng.permutation(40)[:n_build]]
    else:
        bkeys = [np.concatenate([rng.permutation(20), rng.permutation(20)[:4]]),
                 np.arange(n_build) // 20]
    pkeys = [rng.integers(0, 40 if width == 1 else 20, N_PROBE)]
    if width == 2:
        pkeys.append(rng.integers(0, 2, N_PROBE))
    bvalid = [np.ones(n_build, bool) for _ in range(width)]
    pvalid = [np.ones(N_PROBE, bool) for _ in range(width)]
    bsel, psel = np.ones(n_build, bool), np.ones(N_PROBE, bool)
    if case == "null keys":
        bvalid[0][::5] = False
        pvalid[0][::3] = False
    if case == "unselected rows":
        bsel[1::4] = False
        psel[::4] = False
    probe = (np.arange(N_PROBE), pkeys, pvalid, psel)
    build = (np.arange(n_build) + 1000, bkeys, bvalid, bsel)
    return probe, build


def _run(mesh, probe, build, join_type, lookup, hashes=None):
    """``_sharded_probe`` at the probe's own width, jitted: the selected
    (probe row, build row or None) pairs, sorted."""
    def lanes(keys, valid):
        return [x for kd, kv in zip(keys, valid) for x in (jnp.asarray(kd), jnp.asarray(kv))]

    pids, pkeys, pvalid, psel = probe
    bids, bkeys, bvalid, bsel = build
    pk, bk = lanes(pkeys, pvalid), lanes(bkeys, bvalid)
    ph = J.hash_keys(list(zip(pk[::2], pk[1::2])))[0]
    bh = J.hash_keys(list(zip(bk[::2], bk[1::2])))[0] if bids.size else jnp.zeros(0, jnp.int64)
    if hashes is not None:
        ph, bh = hashes(ph, bh)
    n = mesh.devices.size

    @jax.jit
    def go(pcols, bcols, pk, bk, ph, bh, psel, bsel):
        return _sharded_probe(
            mesh, pcols, pk, ph, psel, bcols, bk, bh, bsel, N_PROBE // n,
            join_type, len(pkeys), lookup=lookup,
        )

    cols = lambda ids: [jnp.asarray(ids, jnp.int64), jnp.ones(ids.size, jnp.bool_)]
    outs, osel, ovf = go(cols(pids), cols(bids), pk, bk, ph, bh,
                         jnp.asarray(psel), jnp.asarray(bsel))
    assert not int(ovf)
    p, b, bv, sel = (np.asarray(a) for a in (outs[0], outs[2], outs[3], osel))
    if lookup:
        assert p.tolist() == pids.tolist()  # the probe passes through as it is
    return sorted((int(i), int(j) if v else None) for i, j, v, s in zip(p, b, bv, sel) if s)


def _oracle(probe, build, join_type):
    pids, pkeys, pvalid, psel = probe
    bids, bkeys, bvalid, bsel = build
    by_key = {
        tuple(int(k[r]) for k in bkeys): int(bids[r])
        for r in range(bids.size) if bsel[r] and all(v[r] for v in bvalid)
    }
    pairs = []
    for r in range(N_PROBE):
        if not psel[r]:
            continue
        hit = by_key.get(tuple(int(k[r]) for k in pkeys)) if all(v[r] for v in pvalid) else None
        if hit is not None or join_type == "LEFT":
            pairs.append((int(pids[r]), hit))
    return pairs


CASES = ["plain", "null keys", "unselected rows", "two-column key", "empty build"]


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("join_type", ["INNER", "LEFT"])
@pytest.mark.parametrize("case", CASES)
def test_the_lookup_gives_the_expansions_pairs(case, join_type, devices):
    mesh = make_mesh(devices)
    probe, build = _sides(case)
    got = _run(mesh, probe, build, join_type, lookup=True)
    assert got == _run(mesh, probe, build, join_type, lookup=False)
    assert got == _oracle(probe, build, join_type)
    if case != "empty build":
        assert any(b is not None for _, b in got)


@pytest.mark.parametrize("join_type", ["INNER", "LEFT"])
def test_a_hash_match_whose_keys_differ_is_no_match(join_type):
    """Probe row 0 given build row 0's hash while its key differs: INNER
    drops it, LEFT keeps it as an outer row with no build row."""
    probe, build = _sides("plain")
    probe[1][0][0] = 999  # a key no build row holds
    def collide(ph, bh):
        return ph.at[0].set(bh[0]), bh
    got = _run(make_mesh(1), probe, build, join_type, lookup=True, hashes=collide)
    assert dict(got).get(0, "dropped") == ("dropped" if join_type == "INNER" else None)
    assert got == _oracle(probe, build, join_type)


def test_a_duplicate_build_key_raises_the_flag():
    """Two live build rows under one key: the lookup cannot hold a probe
    row's two pairs and says so; the expansion at twice the width holds them."""
    probe, build = _sides("plain")
    build[1][0][1] = build[1][0][0]
    mesh = make_mesh(1)
    pk = [jnp.asarray(probe[1][0]), jnp.ones(N_PROBE, jnp.bool_)]
    bk = [jnp.asarray(build[1][0]), jnp.ones(N_BUILD, jnp.bool_)]
    ph, bh = J.hash_keys([tuple(pk)])[0], J.hash_keys([tuple(bk)])[0]
    cols = lambda ids: [jnp.asarray(ids, jnp.int64), jnp.ones(ids.size, jnp.bool_)]
    hits = int(np.isin(probe[1][0], build[1][0][:1]).sum())
    assert hits > 0
    _, _, ovf = _sharded_probe(
        mesh, cols(probe[0]), pk, ph, jnp.ones(N_PROBE, jnp.bool_), cols(build[0]), bk,
        bh, jnp.ones(N_BUILD, jnp.bool_), N_PROBE, "INNER", 1, lookup=True)
    assert int(ovf)
    _, osel, ovf = _sharded_probe(
        mesh, cols(probe[0]), pk, ph, jnp.ones(N_PROBE, jnp.bool_), cols(build[0]), bk,
        bh, jnp.ones(N_BUILD, jnp.bool_), 2 * N_PROBE, "INNER", 1)
    assert not int(ovf)
    assert int(osel.sum()) == int(np.isin(probe[1][0], build[1][0]).sum()) + hits


@pytest.mark.parametrize("k", [1, 4])
def test_the_lookup_gathers_no_probe_column(k):
    """``jax.make_jaxpr`` of the join carrying ``k`` probe columns: the lookup
    has at least ``2k`` fewer gathers than the expansion (each column's data
    and validity; and the probe keys', the slot owners' ``shift``)."""
    mesh = make_mesh(1)
    probe, build = _sides("plain")
    pk = [jnp.asarray(probe[1][0]), jnp.ones(N_PROBE, jnp.bool_)]
    bk = [jnp.asarray(build[1][0]), jnp.ones(N_BUILD, jnp.bool_)]
    pcols = [jnp.arange(N_PROBE), jnp.ones(N_PROBE, jnp.bool_)] * k
    bcols = [jnp.arange(N_BUILD), jnp.ones(N_BUILD, jnp.bool_)]

    def gathers(lookup):
        def go(pcols, bcols, pk, bk):
            ph, bh = J.hash_keys([tuple(pk)])[0], J.hash_keys([tuple(bk)])[0]
            return _sharded_probe(
                mesh, pcols, pk, ph, jnp.ones(N_PROBE, jnp.bool_), bcols, bk, bh,
                jnp.ones(N_BUILD, jnp.bool_), N_PROBE, "INNER", 1, lookup=lookup)
        return str(jax.make_jaxpr(go)(pcols, bcols, pk, bk)).count(" gather[")

    assert gathers(False) - gathers(True) >= 2 * k
    assert gathers(True) > 0  # the build's columns are still gathered
