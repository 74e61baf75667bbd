"""Test config: run on a virtual 8-device CPU mesh (no TPU needed).

Mirrors the reference's LocalQueryRunner/DistributedQueryRunner testing tiers
(SURVEY.md §4): full engine in one process, multi-"chip" via XLA host devices.
"""

import os

_platform = os.environ.get("TRINO_TPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
# TRINO_TPU_TEST_DEVICES=1 runs the SINGLE-device lane: the slab /
# fori_loop streaming path (exec/streaming.py) only engages on 1-device
# meshes, i.e. the exact code path that runs on the real chip — an
# 8-device-only CI never sees it (round-4 verdict weak #2)
_devices = os.environ.get("TRINO_TPU_TEST_DEVICES", "8")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={_devices}"
    ).strip()

# Persistent compile cache: `import trino_tpu` below applies the one rule
# (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache); worker
# subprocesses import it too and so share the same warmed cache.

# ── runtime lockdep ─────────────────────────────────────────────────────
# Lock-order + loop-thread-wait validator (trino_tpu/lint/lockdep.py),
# armed for the whole suite unless TT_LOCKDEP=0. Locks created from here
# on are tracked (the interesting ones are per-instance, built during
# tests); scoped to creation sites inside the repo so jax/stdlib
# internals stay untouched. The session-teardown gate below fails the
# run on any recorded problem.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.environ.get("TT_LOCKDEP", "1") != "0":
    from trino_tpu.lint import lockdep as _lockdep

    _lockdep.install(only_paths=(_REPO_ROOT,))

import trino_tpu  # noqa: E402,F401  (enables x64)

import pytest  # noqa: E402

# ── tier-1 shard split ──────────────────────────────────────────────────
# `--tt-shard=K/N` (or TT_TEST_SHARD=K/N) runs only the K-th (1-based) of
# N shards so each CI lane fits the 870 s tier-1 budget. Whole test FILES
# are assigned to shards — never individual tests, so module-scoped
# fixtures (chaos clusters, dbgen caches) are not split across lanes —
# via greedy longest-processing-time packing over rough wall-clock
# weights. Deterministic for a given file set: files are considered in
# (weight desc, name) order and each goes to the currently-lightest
# bucket. Files absent from the table get a small default weight.
_SHARD_WEIGHTS = {
    "test_tpcds_oracle.py": 120,
    "test_dense_join.py": 150,
    "test_sqlite_oracle.py": 100,
    "test_tpcds_suite.py": 90,
    "test_tpch_suite.py": 90,
    "test_fault_tolerance.py": 80,
    "test_spool.py": 20,
    "test_queries.py": 60,
    "test_tpcds_fused.py": 55,
    "test_tpch_fused.py": 55,
    "test_distributed.py": 50,
    "test_skew.py": 45,
    "test_cluster.py": 40,
    "test_observability.py": 40,
    "test_memory_spill.py": 35,
    "test_tpcds.py": 30,
    "test_window.py": 30,
    "test_single_device_lane.py": 30,
    "test_speculation.py": 30,
    "test_result_cache.py": 30,
    "test_flight.py": 30,
}
_SHARD_DEFAULT_WEIGHT = 10

# Measured per-file wall clock from previous runs (seconds), recorded by
# pytest_runtest_logreport below into tests/.tt_timings.json. When a file
# has a measurement, it wins over the static _SHARD_WEIGHTS guess — the
# static table only seeds files that have never run (same unit: rough
# seconds), so shard balance tracks the suite as it grows instead of a
# hand-maintained table going stale.
_TIMINGS_PATH = os.path.join(os.path.dirname(__file__), ".tt_timings.json")
_run_durations: dict = {}  # basename -> seconds accumulated this run


def _load_measured_timings() -> dict:
    import json

    try:
        with open(_TIMINGS_PATH) as f:
            data = json.load(f)
        return {
            k: float(v)
            for k, v in data.items()
            if isinstance(v, (int, float)) and float(v) > 0
        }
    except Exception:
        return {}


def _file_weight(f: str, measured: dict) -> float:
    if f in measured:
        return measured[f]
    return float(_SHARD_WEIGHTS.get(f, _SHARD_DEFAULT_WEIGHT))


def pytest_runtest_logreport(report):
    # all phases (setup/call/teardown) count — module fixtures like chaos
    # clusters dominate some files' wall clock
    try:
        base = os.path.basename(report.location[0])
    except Exception:
        return
    if base.endswith(".py"):
        _run_durations[base] = _run_durations.get(base, 0.0) + float(
            getattr(report, "duration", 0.0) or 0.0
        )


def pytest_sessionfinish(session, exitstatus):
    if not _run_durations:
        return
    import json
    import tempfile

    try:
        data = _load_measured_timings()
        # merge: only files that ran this session are updated, so sharded
        # lanes each refresh their own slice of the table
        for base, dur in _run_durations.items():
            data[base] = round(dur, 3)
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(_TIMINGS_PATH), suffix=".tmp"
        )
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=0, sort_keys=True)
        os.replace(tmp, _TIMINGS_PATH)
    except Exception:
        pass  # timing capture is best-effort; never fail the suite


def pytest_addoption(parser):
    parser.addoption(
        "--tt-shard",
        action="store",
        default=os.environ.get("TT_TEST_SHARD", ""),
        help="K/N — run only the K-th (1-based) of N time-bucketed shards,"
        " split by whole test file",
    )


def _shard_assignment(files, n, measured=None):
    """Map file basename -> shard index (0-based) by LPT packing.
    ``measured`` (basename -> seconds) overrides the static weight table
    per file; defaults to the persisted tests/.tt_timings.json."""
    if measured is None:
        measured = _load_measured_timings()
    order = sorted(
        files, key=lambda f: (-_file_weight(f, measured), f)
    )
    loads = [0.0] * n
    assigned = {}
    for f in order:
        bucket = min(range(n), key=lambda b: (loads[b], b))
        assigned[f] = bucket
        loads[bucket] += _file_weight(f, measured)
    return assigned


#: files whose tests run after every other file's (ROADMAP.md D13): an xdist
#: worker aborts in ``test_ingest.py`` when the tests collected before it are
#: dealt out otherwise than in the runs that passed (PR 36 met it with three
#: more cases in ``test_dense_join.py``, PR 37 with this file in its
#: alphabetical place), so a new file joins the queue at its end
_COLLECTED_LAST = ("test_delivery_account.py", "test_join_capacity.py")


def pytest_collection_modifyitems(config, items):
    late = [i for i in items if os.path.basename(str(i.fspath)) in _COLLECTED_LAST]
    if late:
        items[:] = [i for i in items if i not in late] + late
    spec = config.getoption("--tt-shard")
    if not spec:
        return
    try:
        k_s, n_s = spec.split("/")
        k, n = int(k_s), int(n_s)
    except ValueError:
        raise pytest.UsageError(f"--tt-shard must be K/N, got {spec!r}")
    if not (n >= 1 and 1 <= k <= n):
        raise pytest.UsageError(f"--tt-shard out of range: {spec!r}")
    files = {os.path.basename(str(item.fspath)) for item in items}
    assigned = _shard_assignment(files, n)
    keep, drop = [], []
    for item in items:
        base = os.path.basename(str(item.fspath))
        (keep if assigned[base] == k - 1 else drop).append(item)
    if drop:
        config.hook.pytest_deselected(items=drop)
        items[:] = keep

def pytest_report_header(config):
    # Build the native columnar library ONCE per session (the import
    # compiles it into a sha-keyed cache) and make its absence VISIBLE:
    # a toolchain-less environment silently running every numpy fallback
    # would otherwise look like full native coverage.
    try:
        from trino_tpu import native

        status = (
            "built" if native.NATIVE_AVAILABLE
            else "UNAVAILABLE (numpy fallbacks active)"
        )
    except Exception as e:  # noqa: BLE001 — header must never kill collection
        status = f"import failed: {type(e).__name__}"
    return [f"native columnar library: {status}"]


@pytest.fixture(scope="session", autouse=True)
def lockdep_gate():
    """Fail the session if the runtime lockdep recorded a lock-order
    cycle or an event-loop thread blocking on a lock."""
    yield
    from trino_tpu.lint import lockdep

    if lockdep.installed():
        problems = lockdep.report()
        assert not problems, (
            "runtime lockdep found concurrency problems:\n\n"
            + "\n\n".join(problems)
        )


# Generated-table cache shared across Engine instances. Every
# LocalQueryRunner builds a fresh Engine (fresh connectors), so without
# this each test module re-runs dbgen for the same tiny-schema tables —
# the dominant cost of the tier-1 tail (ROADMAP open item). The caches
# live at session scope and are installed once, before the first runner.
_shared_tpch_batches: dict = {}
_shared_tpch_dicts: dict = {}
_shared_tpcds_batches: dict = {}
_shared_tpcds_dicts: dict = {}


@pytest.fixture(scope="session", autouse=True)
def shared_dbgen_cache():
    from trino_tpu.connectors import tpcds as _tpcds_mod
    from trino_tpu.connectors import tpch as _tpch_mod

    tpch_init = _tpch_mod.TpchConnector.__init__

    def shared_tpch_init(self, *a, **kw):
        tpch_init(self, *a, **kw)
        self._batch_cache = _shared_tpch_batches
        self._dict_cache = _shared_tpch_dicts

    tpcds_init = _tpcds_mod.TpcdsConnector.__init__

    def shared_tpcds_init(self, *a, **kw):
        tpcds_init(self, *a, **kw)
        self._dict_cache = _shared_tpcds_dicts

    # TpcdsConnector has no batch cache of its own: memoize read_split
    # (split generation is deterministic — seeded rngs keyed on the split)
    tpcds_read = _tpcds_mod.TpcdsConnector.read_split

    def cached_tpcds_read(self, schema, table, columns, split):
        key = (schema, table, tuple(columns), split.index, split.total)
        hit = _shared_tpcds_batches.get(key)
        if hit is None:
            hit = tpcds_read(self, schema, table, columns, split)
            _shared_tpcds_batches[key] = hit
        return hit

    _tpch_mod.TpchConnector.__init__ = shared_tpch_init
    _tpcds_mod.TpcdsConnector.__init__ = shared_tpcds_init
    _tpcds_mod.TpcdsConnector.read_split = cached_tpcds_read
    try:
        yield
    finally:
        _tpch_mod.TpchConnector.__init__ = tpch_init
        _tpcds_mod.TpcdsConnector.__init__ = tpcds_init
        _tpcds_mod.TpcdsConnector.read_split = tpcds_read
