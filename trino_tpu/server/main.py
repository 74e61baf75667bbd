"""Server entry point: ``python -m trino_tpu.server.main``.

Reference: ``server/Server.java:73`` — one binary, coordinator vs worker by
config. Workers take ``--discovery`` pointing at the coordinator and
announce themselves (DiscoveryNodeManager analog in server/cluster.py).
"""

from __future__ import annotations

import argparse
import signal
import sys
import time


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="trino-tpu server")
    parser.add_argument("--role", choices=["coordinator", "worker"], default="coordinator")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--node-id", default=None)
    parser.add_argument("--discovery", default=None, help="coordinator URI (workers)")
    parser.add_argument(
        "--platform",
        default=None,
        help="JAX platform of this process, set before engine start. For "
        "workers of CPU test clusters (--platform cpu); a server on the "
        "chip takes JAX's default and fails at start-up without one",
    )
    parser.add_argument(
        "--spmd-coordinator",
        default=None,
        help="jax.distributed coordinator host:port (enables multi-host SPMD)",
    )
    parser.add_argument("--spmd-procs", type=int, default=0)
    parser.add_argument("--spmd-rank", type=int, default=0)
    parser.add_argument(
        "--catalog",
        action="append",
        default=[],
        help="register a catalog: name=kind[:arg] (etc/catalog analog)",
    )
    parser.add_argument(
        "--cluster-memory-limit-bytes",
        type=int,
        default=None,
        help="coordinator-enforced cluster-wide memory ceiling",
    )
    parser.add_argument(
        "--max-inflight-requests",
        type=int,
        default=None,
        help="global ceiling on concurrently handled external requests"
        " (excess shed with 503 + Retry-After)",
    )
    parser.add_argument(
        "--tenant-rate-limit-qps",
        type=float,
        default=None,
        help="per-tenant statement token-bucket refill rate (0 disables)",
    )
    parser.add_argument(
        "--client-timeout-s",
        type=float,
        default=None,
        help="cancel a query unpolled by its client for this long",
    )
    parser.add_argument(
        "--result-page-max-bytes",
        type=int,
        default=None,
        help="byte budget per streamed result page (0 = materialized)",
    )
    args = parser.parse_args(argv)

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    if args.spmd_coordinator:
        # must run before any jax computation initializes backends
        from trino_tpu.parallel.spmd import initialize_spmd

        initialize_spmd(args.spmd_coordinator, args.spmd_procs, args.spmd_rank)

    from trino_tpu.server.http import TrinoTpuServer

    engine = None
    if args.catalog:
        from trino_tpu.connectors.api import register_catalog_spec
        from trino_tpu.engine import Engine

        engine = Engine()
        for spec in args.catalog:
            register_catalog_spec(engine.catalogs, spec)

    server_config = None
    overrides = {
        "max_inflight_requests": args.max_inflight_requests,
        "tenant_rate_limit_qps": args.tenant_rate_limit_qps,
        "client_timeout_s": args.client_timeout_s,
        "result_page_max_bytes": args.result_page_max_bytes,
    }
    if any(v is not None for v in overrides.values()):
        from trino_tpu.config import ServerConfig

        server_config = ServerConfig(
            **{k: v for k, v in overrides.items() if v is not None}
        )

    server = TrinoTpuServer(
        engine=engine,
        host=args.host,
        port=args.port,
        role=args.role,
        node_id=args.node_id,
        discovery_uri=args.discovery,
        spmd=bool(args.spmd_coordinator),
        cluster_memory_limit_bytes=args.cluster_memory_limit_bytes,
        server_config=server_config,
    )
    server.start()
    # parent supervisors (tests, orchestration) read this line
    print(f"LISTENING {server.base_uri}", flush=True)

    stop = {"flag": False}

    def on_term(_sig, _frm):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)
    try:
        # a drained worker (PUT /v1/info/state SHUTTING_DOWN) stops its
        # server itself; the process must then exit so rolling restarts
        # can respawn it
        while not stop["flag"] and server.state != "STOPPED":
            time.sleep(0.2)
    finally:
        if server.state != "STOPPED":
            server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
