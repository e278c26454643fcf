"""Operator CLI: the deployment entry points, one per process role.

The reference deploys via docker-compose with one container per service
(``docker-compose.services.yml``); here the roles are subcommands of one
package CLI (used by ``deploy/docker-compose.yml``):

    python -m copilot_for_consensus_tpu serve        # pipeline + gateway
    python -m copilot_for_consensus_tpu broker       # durable bus broker
    python -m copilot_for_consensus_tpu retry-job    # stuck-doc requeue
    python -m copilot_for_consensus_tpu failed-queues list ...
"""

from __future__ import annotations

import argparse
import json
import pathlib
import signal
import sys
import threading


def _load_config(path: str | None) -> dict:
    if not path:
        return {}
    text = pathlib.Path(path).read_text()
    if path.endswith((".yml", ".yaml")):
        import yaml

        return yaml.safe_load(text) or {}
    return json.loads(text)


def _cmd_serve(args: argparse.Namespace) -> int:
    from copilot_for_consensus_tpu.services.bootstrap import serve_pipeline

    cfg = _load_config(args.config)
    # An EMPTY multihost section (or `true`) means TPU-pod
    # auto-discovery, so plain truthiness is the wrong gate; `false` /
    # `null` explicitly disable.
    mh = cfg.get("multihost")
    if mh is not None and mh is not False:
        # Must join the distributed runtime BEFORE any engine triggers a
        # device query — jax.devices() then spans the whole slice/pod.
        from copilot_for_consensus_tpu.parallel.multihost import (
            initialize_multihost,
        )
        initialize_multihost(mh)
    # Host-only deployments (mock/openai drivers) never import jax; a
    # tpu driver names the device it serves on and keeps its compiled
    # programs across restarts (parallel/mesh.py).
    on_chip = any(dict(cfg.get(k) or {}).get("driver") == "tpu"
                  for k in ("embedding", "vector_store", "llm"))
    device: dict = {}
    if on_chip:
        from copilot_for_consensus_tpu.parallel.mesh import (
            enable_compile_cache,
            require_accelerator,
        )
        device["compile_cache"] = enable_compile_cache()
        dev = require_accelerator("serve with a tpu driver")
        device.update(platform=dev.platform, device_kind=dev.device_kind)
    server = serve_pipeline(cfg, host=args.host, port=args.port)
    server.start()
    print(json.dumps({"event": "serving", "host": args.host,
                      "port": server.port, **device}), flush=True)
    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    # Graceful drain (services/lifecycle.py; docs/runbooks/
    # rolling-restart.md): readiness flips 503 FIRST, pools stop
    # consuming (nothing nacked — the broker redelivers nothing after
    # a clean drain), the engine finishes active slots up to the
    # drain deadline then evacuates-and-journals the rest, the publish
    # outbox flushes, and only then does the process exit. A second
    # signal during the drain is absorbed (the stop event is already
    # set); SIGKILL remains the hard path the engine journal exists
    # for.
    report = server.drain()
    print(json.dumps({"event": "drained", **report}), flush=True)
    return 0


def _cmd_retry_job(args: argparse.Namespace) -> int:
    from copilot_for_consensus_tpu.bus.factory import create_publisher
    from copilot_for_consensus_tpu.storage.factory import (
        create_document_store,
    )
    from copilot_for_consensus_tpu.tools.retry_job import (
        RetryStuckDocumentsJob,
    )

    cfg = _load_config(args.config)
    store = create_document_store(cfg.get("document_store",
                                          {"driver": "sqlite"}))
    store.connect()
    pub = create_publisher(cfg.get("bus", {"driver": "broker"}))
    pub.connect()
    from copilot_for_consensus_tpu.obs.metrics import (
        create_metrics_collector,
    )
    job = RetryStuckDocumentsJob(
        store, pub,
        metrics=create_metrics_collector(cfg.get("metrics")))
    if args.once:
        print(json.dumps({"event": "retry_sweep", **job.run_once()}),
              flush=True)
        return 0
    job.run_loop(interval_seconds=args.interval)
    return 0


def _cmd_migrate(args: argparse.Namespace) -> int:
    from copilot_for_consensus_tpu.storage.factory import (
        create_document_store,
    )
    from copilot_for_consensus_tpu.tools.data_migration import (
        export_data,
        import_data,
    )
    from copilot_for_consensus_tpu.vectorstore.factory import (
        create_vector_store,
    )

    cfg = _load_config(args.config)
    store = create_document_store(cfg.get("document_store",
                                          {"driver": "sqlite"}))
    store.connect()
    # The vector leg only makes sense against a durable index: exporting
    # a freshly-constructed empty store would clobber a previous dump
    # while printing success, and an import that never save()s is lost
    # at process exit — so both ends key off persist_path.
    vs_cfg = dict(cfg.get("vector_store") or {})
    persist = vs_cfg.get("persist_path")
    vs = None
    if vs_cfg and persist:
        vs = create_vector_store(vs_cfg)
        if args.cmd == "export-data":
            if pathlib.Path(persist).exists():
                vs.load(persist)
            else:
                print(json.dumps({"event": "vector_export_skipped",
                                  "reason": f"no index at {persist}"}),
                      flush=True)
                vs = None
    elif vs_cfg:
        print(json.dumps({"event": "vector_leg_skipped",
                          "reason": "vector_store.persist_path not set"}),
              flush=True)
    fn = export_data if args.cmd == "export-data" else import_data
    counts = fn(store, args.dir, vector_store=vs)
    if vs is not None and args.cmd == "import-data":
        vs.save(persist)
    print(json.dumps({"event": args.cmd.replace("-", "_"), **counts}),
          flush=True)
    return 0


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    ap = argparse.ArgumentParser(prog="copilot_for_consensus_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)

    serve = sub.add_parser("serve", help="pipeline + unified gateway")
    serve.add_argument("--config", default=None,
                       help="JSON/YAML pipeline config")
    serve.add_argument("--host", default="0.0.0.0")
    serve.add_argument("--port", type=int, default=8080)

    sub.add_parser("broker", help="durable bus broker",
                   add_help=False)

    retry = sub.add_parser("retry-job", help="stuck-document requeue")
    retry.add_argument("--config", default=None)
    retry.add_argument("--interval", type=float, default=300.0)
    retry.add_argument("--once", action="store_true")

    sub.add_parser("failed-queues", help="failed-queue operator CLI",
                   add_help=False)

    sub.add_parser("logmine", help="mine templates from JSON logs",
                   add_help=False)

    sub.add_parser("logstore", help="log aggregation sink + query API "
                   "(Loki/Promtail role)", add_help=False)

    sub.add_parser("exporters", help="store/vector stats exporter",
                   add_help=False)

    sub.add_parser("tracepath", help="pipeline trace critical-path "
                   "analyzer (bottleneck stage)", add_help=False)

    sub.add_parser("slo", help="SLO scoreboard over telemetry spools "
                   "(merged registries, error-budget burn)",
                   add_help=False)

    for name, hlp in (("export-data", "dump all collections to JSONL"),
                      ("import-data", "load a JSONL dump")):
        mig = sub.add_parser(name, help=hlp)
        mig.add_argument("--config", default=None)
        mig.add_argument("--dir", required=True,
                         help="dump directory (out for export, src for "
                              "import)")

    # Delegating subcommands keep their own argparsers: split argv at the
    # subcommand and hand the rest through untouched.
    if argv and argv[0] == "broker":
        from copilot_for_consensus_tpu.bus.broker import main as broker_main

        return broker_main(argv[1:])
    if argv and argv[0] == "failed-queues":
        from copilot_for_consensus_tpu.tools.failed_queues import (
            main as fq_main,
        )

        return fq_main(argv[1:])
    if argv and argv[0] == "logmine":
        from copilot_for_consensus_tpu.tools.logmine import main as lm_main

        return lm_main(argv[1:])
    if argv and argv[0] == "logstore":
        from copilot_for_consensus_tpu.tools.logstore import (
            main as ls_main,
        )

        return ls_main(argv[1:])
    if argv and argv[0] == "exporters":
        from copilot_for_consensus_tpu.tools.exporters import main as ex_main

        return ex_main(argv[1:])
    if argv and argv[0] == "tracepath":
        from copilot_for_consensus_tpu.tools.tracepath import (
            main as tp_main,
        )

        return tp_main(argv[1:])
    if argv and argv[0] == "slo":
        from copilot_for_consensus_tpu.obs.slo import main as slo_main

        return slo_main(argv[1:])

    args = ap.parse_args(argv)
    if args.cmd == "serve":
        return _cmd_serve(args)
    if args.cmd == "retry-job":
        return _cmd_retry_job(args)
    if args.cmd in ("export-data", "import-data"):
        return _cmd_migrate(args)
    raise AssertionError(args.cmd)


if __name__ == "__main__":
    raise SystemExit(main())
