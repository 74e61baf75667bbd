"""Test harness: LocalQueryRunner / DistributedQueryRunner analogs.

Reference: ``core/trino-main/src/main/java/io/trino/testing/LocalQueryRunner.java:221,631``
(single-process full stack) and
``testing/trino-testing/.../DistributedQueryRunner.java:72`` (N workers in
one process — here N mesh shards with real collectives). Both delegate to
:class:`trino_tpu.engine.Engine`, the same core the HTTP server serves.
The correctness oracle is NumPy recomputation over the same generated data
(the reference's H2-oracle pattern, ``H2QueryRunner.java``).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

from trino_tpu.config import Session
from trino_tpu.engine import Engine
from trino_tpu.planner import plan as P
from trino_tpu.sql import parse_statement


class LocalQueryRunner:
    """Parse -> analyze/plan -> execute, one process, no RPC."""

    def __init__(
        self, session: Optional[Session] = None, engine: Optional[Engine] = None
    ):
        self.session = session or Session()
        # sharing an engine across runners shares connector state/caches
        # (the reference's QueryRunner-over-TestingTrinoServer pattern)
        self.engine = engine or Engine()

    @property
    def catalogs(self):
        return self.engine.catalogs

    @property
    def memory_pool(self):
        return self.engine.memory_pool

    def plan(self, sql: str) -> P.PlanNode:
        return self.engine.plan(parse_statement(sql), self.session)

    def execute(self, sql: str) -> tuple[list[tuple], list[str]]:
        res = self.engine.execute_statement(sql, self.session)
        return res.rows, res.column_names

    def explain(self, sql: str) -> str:
        return P.plan_text(self.plan(sql))

    def assert_query(self, sql: str, expected: Sequence[tuple], ordered: bool = False):
        rows, _ = self.execute(sql)
        got = rows if ordered else sorted(map(tuple, rows))
        want = list(expected) if ordered else sorted(map(tuple, expected))
        assert got == want, f"query mismatch:\n got: {got[:20]}\nwant: {want[:20]}"


class DistributedQueryRunner(LocalQueryRunner):
    """Multi-shard runner over a device mesh: every query executes SPMD
    with real collectives between shards."""

    def __init__(self, session: Optional[Session] = None, n_devices: Optional[int] = None):
        super().__init__(session)
        from trino_tpu.parallel.mesh import make_mesh

        self.mesh = make_mesh(n_devices)
        self.engine.mesh = self.mesh
        self.session.set("execution_mode", "distributed")


class MultiProcessQueryRunner:
    """N separate server *processes* — a coordinator and N-1 workers — with
    queries flowing through real HTTP task dispatch and page exchange.

    Reference: ``testing/trino-testing/.../DistributedQueryRunner.java:72``
    (N real TestingTrinoServer instances; here real OS processes, which is
    stricter: nothing can leak through shared memory).

    ``platform`` (default ``"cpu"``) is the workers' ``JAX_PLATFORMS``: CPU
    test clusters only; never with the chip held by the parent. A chip
    belongs to one process, and this parent has already imported JAX.
    """

    def __init__(
        self,
        n_workers: int = 2,
        platform: str = "cpu",
        spmd: bool = False,
        cluster_memory_limit_bytes: Optional[int] = None,
        catalogs: Optional[list] = None,
    ):
        import os
        import subprocess
        import time
        import urllib.request

        import secrets as _secrets

        self._procs: list[subprocess.Popen] = []
        self.spmd = spmd
        self.platform = platform
        env = dict(os.environ)
        # one internal credential per PROCESS (not per cluster): rotating
        # it would 401 the parent's calls to an older still-live cluster
        from trino_tpu.server.auth import ENV_VAR as _AUTH_ENV

        if not os.environ.get(_AUTH_ENV):
            os.environ[_AUTH_ENV] = _secrets.token_hex(16)
        env[_AUTH_ENV] = os.environ[_AUTH_ENV]
        # workers share the parent's persistent compile cache (a cold
        # worker cache makes first-query compiles race the exchange
        # timeouts): they inherit the environment, and `import trino_tpu`
        # gives every process of a checkout the same directory
        env["JAX_PLATFORMS"] = platform

        self._logs: list[list[str]] = []
        self._env = env
        self._cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        popen = self._popen
        await_listening = self._await_listening

        spmd_args: list[list[str]] = []
        if spmd:
            # one jax.distributed group: coordinator = rank 0
            import socket

            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            dist_port = s.getsockname()[1]
            s.close()
            nprocs = n_workers + 1
            spmd_args = [
                [
                    "--spmd-coordinator",
                    f"127.0.0.1:{dist_port}",
                    "--spmd-procs",
                    str(nprocs),
                    "--spmd-rank",
                    str(rank),
                ]
                for rank in range(nprocs)
            ]

        catalog_args: list[str] = []
        for spec in catalogs or []:
            catalog_args += ["--catalog", spec]
        self._catalog_args = catalog_args
        coord_args = ["--role", "coordinator", "--platform", platform]
        coord_args += catalog_args
        if cluster_memory_limit_bytes is not None:
            coord_args += [
                "--cluster-memory-limit-bytes", str(cluster_memory_limit_bytes)
            ]
        coord_proc = popen(coord_args + (spmd_args[0] if spmd else []))
        if spmd:
            # workers must join the jax.distributed group before any process
            # finishes booting; spawn all before reading LISTENING lines.
            # Workers discover the coordinator lazily via --discovery-wait.
            self.coordinator_uri = None
            worker_procs = [
                popen(
                    [
                        "--role",
                        "worker",
                        "--node-id",
                        f"worker-{i}",
                        "--discovery",
                        "@coordinator",
                        "--platform",
                        platform,
                    ]
                    + catalog_args
                    + spmd_args[i + 1]
                )
                for i in range(n_workers)
            ]
            self.coordinator_uri = await_listening(coord_proc)
            self._worker_procs = worker_procs
            self.worker_uris = [await_listening(p) for p in worker_procs]
            # late discovery: tell each worker where the coordinator is
            import json as _json

            from trino_tpu.server import auth as _auth

            for uri in self.worker_uris:
                req = urllib.request.Request(
                    f"{uri}/v1/discovery",
                    data=_json.dumps(
                        {"uri": self.coordinator_uri}
                    ).encode(),
                    method="PUT",
                    headers=_auth.headers(),
                )
                urllib.request.urlopen(req, timeout=10)
        else:
            self.coordinator_uri = await_listening(coord_proc)
            self._worker_procs = [
                popen(self._worker_args(i)) for i in range(n_workers)
            ]
            self.worker_uris = [
                await_listening(p) for p in self._worker_procs
            ]
        # wait for every worker to be announced and healthy
        deadline = time.time() + 60
        import json as _json

        while time.time() < deadline:
            with urllib.request.urlopen(f"{self.coordinator_uri}/v1/node") as r:
                info = _json.loads(r.read().decode())
            if len(info.get("nodes", [])) >= n_workers:
                break
            time.sleep(0.2)
        else:
            raise TimeoutError("workers did not announce in time")

    def _popen(self, args):
        import subprocess
        import sys

        proc = subprocess.Popen(
            [sys.executable, "-m", "trino_tpu.server.main", *args],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=self._env,
            cwd=self._cwd,
        )
        self._procs.append(proc)
        return proc

    def _await_listening(self, proc):
        import threading
        import time

        deadline = time.time() + 180
        while time.time() < deadline:
            line = proc.stdout.readline()
            if line.startswith("LISTENING "):
                # keep draining the pipe: an undrained 64KB pipe buffer
                # blocks the child on its next write and freezes it
                log: list[str] = []
                self._logs.append(log)

                def drain(stream=proc.stdout, log=log):
                    for ln in stream:
                        log.append(ln)

                threading.Thread(target=drain, daemon=True).start()
                return line.split()[1].strip()
            if proc.poll() is not None:
                raise RuntimeError(
                    f"server process exited: {proc.stdout.read()}"
                )
        raise TimeoutError("server did not start in time")

    def _worker_args(self, i: int) -> list[str]:
        return [
            "--role", "worker",
            "--node-id", f"worker-{i}",
            "--discovery", self.coordinator_uri,
            "--platform", self.platform,
        ] + self._catalog_args

    def execute(self, sql: str, session_properties: Optional[dict] = None):
        from trino_tpu.client import ClientSession, StatementClient

        cs = ClientSession(
            properties={"execution_mode": "cluster", **(session_properties or {})}
        )
        client = StatementClient(self.coordinator_uri, sql, cs)
        rows = list(client.rows())
        names = [c.name for c in client.columns] if client.columns else []
        return rows, names

    # --- chaos / lifecycle hooks (non-SPMD clusters only) ----------------

    def kill_worker(self, i: int, timeout: float = 10.0) -> None:
        """SIGKILL worker ``i`` — no drain, no goodbye; simulates node
        death for spool/lineage recovery tests."""
        p = self._worker_procs[i]
        p.kill()
        p.wait(timeout=timeout)

    def drain_worker(self, i: int, timeout: float = 120.0) -> None:
        """Graceful decommission: ``PUT /v1/info/state SHUTTING_DOWN``
        stops admission, finishes running tasks, force-spools retained
        buffers, deregisters, and exits the process."""
        import urllib.request

        from trino_tpu.server import auth as _auth

        req = urllib.request.Request(
            f"{self.worker_uris[i]}/v1/info/state",
            data=b'"SHUTTING_DOWN"',
            method="PUT",
            headers=_auth.headers(),
        )
        urllib.request.urlopen(req, timeout=10)
        self._worker_procs[i].wait(timeout=timeout)

    def restart_worker(self, i: int, timeout: float = 60.0) -> str:
        """Respawn worker ``i`` (same node id, fresh port) and wait until
        the coordinator has re-registered its announce."""
        import json as _json
        import time
        import urllib.request

        proc = self._popen(self._worker_args(i))
        uri = self._await_listening(proc)
        self._worker_procs[i] = proc
        self.worker_uris[i] = uri
        deadline = time.time() + timeout
        while time.time() < deadline:
            with urllib.request.urlopen(
                f"{self.coordinator_uri}/v1/node", timeout=10
            ) as r:
                info = _json.loads(r.read().decode())
            for n in info.get("nodes", []):
                if n.get("nodeId") == f"worker-{i}" and n.get("uri") == uri:
                    return uri
            time.sleep(0.2)
        raise TimeoutError(f"worker-{i} did not re-announce in time")

    def close(self) -> None:
        for p in self._procs:
            p.terminate()
        for p in self._procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                p.kill()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
