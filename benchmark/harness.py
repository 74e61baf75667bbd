"""One run of one cell: boot the server, warm, measure a window, compare.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name ``BENCHMARK.json``
gives: ``configs/<config>.json`` (the entry's ``file``), ``traffic/<mix>.json``
with ``templates/<t>.sql|json``, ``metrics/<metric>.py``; and on the side that
decides ``correct``, ``references/<name>.py`` (a template's ``"reference"``) and
the column providers of ``datasets/<dataset>/`` (a configuration's
``"dataset"``). A later PR adds a cell by adding such files and an entry;
nothing here names a cell, a query, a table or a column.

The system under test is the served SQL path and nothing else of the
program: ``TrinoTpuServer(port=0)`` in this process, and one
``trino_tpu.client.Connection`` per stream whose ``execute(sql)`` (POST
/v1/statement, then nextUri to the last page) is the entry the window
drives. ``correct`` is decided on what those same calls returned.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import threading
import time
import traceback

from benchmark import compare, reference, refdata, stats, tracereduce, traffic
from benchmark.files import Refused, load_module

#: a traced run's window is the traced slice: it ends with the first answer
#: that comes this long after t0 (or after --seconds, where that is less) ...
SLICE_MIN_S = 3.0
#: ... and the profiler stops here, in the middle of a query, where one query
#: is longer (the query in flight then ends the window)
SLICE_MAX_S = 10.0


def log(message: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {message}", file=sys.stderr, flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"BENCHMARK.json has no {what} named {name!r}")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(data_root: str, name: str):
    path = os.path.join(data_root, "metrics", name + ".py")
    return load_module(path, f"reader of the metric {name!r}").read


@contextlib.contextmanager
def request_spans(client_module, annotate):
    """Name the client's HTTP round trips in the profiler's trace, for the
    traced run only: ``bench:submit`` and ``bench:poll``."""
    plain = client_module.StatementClient._request_once

    def spanned(self, method, uri, body=None):
        with annotate("bench:submit" if method == "POST" else "bench:poll"):
            return plain(self, method, uri, body)

    client_module.StatementClient._request_once = spanned
    try:
        yield
    finally:
        client_module.StatementClient._request_once = plain


class Tracer:
    """The profiler around the first seconds of a window (``--trace 1``)."""

    def __init__(self, jax, directory: str):
        self.jax = jax
        self.directory = directory
        self.lock = threading.Lock()
        self.running = False
        self.timer = None

    def annotate(self, name):
        return self.jax.profiler.TraceAnnotation(name)

    def start(self):
        shutil.rmtree(self.directory, ignore_errors=True)
        options = self.jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the Python tracer floods the file
        options.host_tracer_level = 1
        self.jax.profiler.start_trace(self.directory, profiler_options=options)
        self.running = True

    def mark_start(self):
        with self.annotate("bench:slice_start"):
            pass
        self.timer = threading.Timer(SLICE_MAX_S, self.stop)
        self.timer.daemon = True
        self.timer.start()

    def stop(self):
        with self.lock:
            if not self.running:
                return
            self.running = False
            with self.annotate("bench:slice_end"):
                pass
            self.jax.profiler.stop_trace()
        if self.timer is not None:
            self.timer.cancel()

    def reduce(self):
        try:
            return tracereduce.reduce(tracereduce.read(tracereduce.find_xplane(self.directory)))
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)


def load_cell(root: str, workload: str):
    """What ``BENCHMARK.json`` and the files it names say of one cell. A
    template whose reference function is not there, or that reads a column
    with no provider (or two), is refused here, before anything boots."""
    bench = traffic.load_json(os.path.join(root, "BENCHMARK.json"))
    data_root = os.path.join(root, bench["paths"][0])
    cell = find(bench["workloads"], workload, "workload")
    config_entry = find(bench["configs"], cell["config"], "config")
    config = traffic.load_json(os.path.join(root, config_entry["file"]))
    mix = traffic.Mix(data_root, cell["traffic"])
    for template in mix.templates.values():
        reference.load_function(data_root, template.meta["reference"])
    dataset, reads = dataset_and_reads(data_root, config, mix)
    dataset.check(reads)
    return bench, data_root, cell, config, mix


def dataset_and_reads(data_root: str, config: dict, mix):
    """The configuration's data set and the columns the mix's templates read."""
    if "dataset" not in config:
        raise Refused(f"the configuration {config.get('name')!r} names no \"dataset\"")
    return (refdata.Dataset(data_root, config["dataset"]),
            refdata.union_of_reads(mix.templates.values()))


def reference_columns(root: str, data_root: str, config: dict, mix, cache: bool = True):
    """The reference's columns that the mix's templates read."""
    cache_dir = os.path.join(root, ".cache", "benchmark") if cache else None
    dataset, reads = dataset_and_reads(data_root, config, mix)
    return dataset.load(config["scale_factor"], reads, cache_dir)


def run_cell(args, *, root: str, platform: str, started: float) -> dict:
    bench, data_root, cell, config, mix = load_cell(root, args.workload)
    if not os.path.isdir(os.path.join(root, "trino_tpu")):
        raise Refused(f"no program to measure: {root}/trino_tpu is not there")
    if args.seconds <= 0:
        raise Refused("--seconds must be above 0")

    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise Refused(
            f"needs a {platform} device, but JAX found {devices[0].platform!r} "
            f"({len(devices)} device(s))"
        )
    if len(devices) != cell["chips"]:
        raise Refused(f"cell {cell['name']} asks for {cell['chips']} chip(s), JAX found {len(devices)}")
    kind = devices[0].device_kind
    peaks = traffic.load_json(os.path.join(data_root, "peaks.json")).get(kind)
    if peaks is None and platform == "tpu":
        raise Refused(f"device kind {kind!r} is not in {data_root}/peaks.json")

    import trino_tpu  # noqa: F401  (sets the compile cache by the program's own rule)
    from trino_tpu import client as client_module
    from trino_tpu.server.http import TrinoTpuServer

    log(f"cell {cell['name']} seed {args.seed} on {len(devices)} x {kind}; "
        f"compile cache {jax.config.jax_compilation_cache_dir}")
    schema = f"{config['catalog']}.{config['schema']}"
    tracer = None
    records: list[dict] = []
    warmups = []
    server = TrinoTpuServer(port=0).start()
    try:
        conns = [
            client_module.Connection(
                server.base_uri,
                client_module.ClientSession(
                    catalog=config["catalog"], schema=config["schema"],
                    properties=dict(config["session"]),
                ),
            )
            for _ in mix.streams
        ]
        # warm-up: each template once cold (the validation parameters), once
        # warm (other literals): every program the window will call
        for name, template in mix.templates.items():
            for params in (template.meta["validation"], mix.grids[name][-1]):
                a = time.perf_counter()
                conns[0].execute(template.sql(schema, params))
                warmups.append({"template": name, "seconds": time.perf_counter() - a})
                log(f"warm-up {name} {params}: {warmups[-1]['seconds']:.3f} s")
        seen = {q["queryId"] for q in conns[0].list_queries()} if args.trace else set()

        t0 = None
        seconds = min(args.seconds, SLICE_MIN_S) if args.trace else args.seconds
        if args.trace:
            tracer = Tracer(jax, os.path.join(root, ".cache", "benchmark", "trace"))
            tracer.start()
        annotate = tracer.annotate if tracer else (lambda name: contextlib.nullcontext())
        lock = threading.Lock()

        def stream(i: int):
            for name, params in mix.schedule(args.seed, i):
                if time.perf_counter() - t0 >= seconds:
                    return
                sql = mix.templates[name].sql(schema, params)
                rec = {"stream": i, "template": name, "params": params, "sql": sql,
                       "rows": None, "error": None}
                rec["start"] = time.perf_counter()
                try:
                    with annotate("bench:begin"):
                        pass  # found again where the trace ends inside the query
                    with annotate("bench:execute"):
                        rec["rows"], _ = conns[i].execute(sql)
                except Exception:  # noqa: BLE001  (a failed query is a result)
                    rec["error"] = traceback.format_exc(limit=3)
                rec["end"] = time.perf_counter()
                with lock:
                    records.append(rec)
                if mix.think_s:
                    time.sleep(mix.think_s)

        threads = [threading.Thread(target=stream, args=(i,), name=f"stream-{i}")
                   for i in range(len(mix.streams))]
        with request_spans(client_module, annotate) if tracer else contextlib.nullcontext():
            t0 = time.perf_counter()
            if tracer:
                tracer.mark_start()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if tracer:
                tracer.stop()
        setup_s = t0 - started
        window_s = max(r["end"] for r in records) - t0
        # the peak on the fullest chip
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
        infos = []
        if args.trace:
            infos = [q for q in conns[0].list_queries() if q["queryId"] not in seen]
    finally:
        if tracer:
            tracer.stop()
        server.stop()
    log(f"set-up {setup_s:.3f} s, window {window_s:.3f} s, {len(records)} queries")

    # the reference, once the window has closed and the peak has been read
    a = time.perf_counter()
    ref = reference.Reference(reference_columns(root, data_root, config, mix), data_root=data_root)

    def answers(name, params):
        return ref.answer(mix.templates[name].meta["reference"], params)

    sort_keys = {n: t.meta["sort_key"] for n, t in mix.templates.items()}
    verdict = compare.decide(records, answers, sort_keys)
    log(f"reference: {len(ref.answers)} distinct answers in {time.perf_counter() - a:.3f} s")

    done = len(records)
    failed = sum(1 for r in records if r["rows"] is None)
    latencies = [r["end"] - r["start"] for r in records]
    run = {
        "config": config, "mix": mix, "records": records, "infos": infos,
        "window_s": window_s, "peaks": peaks, "trace": None,
        "query_s": window_s * len(mix.streams) / done,
    }
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": verdict["correct"], "attempted": done, "failed": failed}
    metrics = {}
    if args.trace:
        run["trace"] = tracer.reduce()
        if run["trace"] is None:
            raise Refused("the traced slice holds no operation on a device")
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        for m in bench["per_layer"]:
            if applies(m, cell["name"]):
                value = load_reader(data_root, m["name"])(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {
            "query_s": run["query_s"],
            "qph": done * 3600.0 / window_s,
            "p95_s": stats.percentile(latencies, 95),
            "setup_s": setup_s,
        }
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if run["trace"]:
        result["breakdown"] = run["trace"]["breakdown"]
    result["warmup_s"] = [w["seconds"] for w in warmups]
    result["compared"] = verdict["compared"]
    for r in records:
        if r["error"]:
            log(f"failed query ({r['template']} {r['params']}): {r['error']}")
            break
    for name, c in verdict["compared"].items():
        print(f"compared {name} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv, *, root: str, platform: str, started: float) -> int:
    args = parse_args(argv)
    try:
        result = run_cell(args, root=root, platform=platform, started=started)
    except Refused as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0
