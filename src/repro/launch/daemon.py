"""Tuning-service daemon CLI: run a multi-tenant fleet behind a socket.

Starts a ``TuningDaemon`` — one worker pool, one elastic fleet, one
shared (optionally sharded) config/model corpus — listening for
JSON-lines tuning requests on localhost.  SIGINT/SIGTERM drain
gracefully: in-flight empirical tests finish, unfinished jobs resolve as
cancelled partials, the store is flushed.

    # sharded corpus, 4 thread workers, ephemeral port printed on start
    PYTHONPATH=src python -m repro.launch.daemon \
        --store-dir corpus/ --backend thread --workers 4 --port 7421

    # talk to it
    python -m repro.launch.serve --autotune --ticks 40 \
        --service 127.0.0.1:7421

Per-tenant worker-seconds budgets arrive with the requests themselves
(``tenant_budget_s`` on submit); ``--default-tenant-budget`` applies one
to tenants that never declare any.
"""
from __future__ import annotations

import argparse
import json


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0: bind an ephemeral port and print it")
    ap.add_argument("--backend", default="thread",
                    choices=("virtual", "thread", "subprocess"))
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--store-dir", default=None,
                    help="sharded corpus directory (the default)")
    ap.add_argument("--store", default=None,
                    help="single-file ConfigStore path instead of a "
                    "sharded corpus")
    ap.add_argument("--shards", type=int, default=4,
                    help="shard count when creating a new --store-dir")
    ap.add_argument("--budget", type=int, default=16,
                    help="default per-request trial budget")
    ap.add_argument("--max-active-jobs", type=int, default=32)
    ap.add_argument("--max-tenants", type=int, default=64)
    ap.add_argument("--max-active-per-tenant", type=int, default=4)
    ap.add_argument("--max-queued-per-tenant", type=int, default=16)
    ap.add_argument("--default-tenant-budget", type=float, default=None,
                    help="worker-seconds budget for tenants that never "
                    "declare one (default: unlimited)")
    ap.add_argument("--in-flight", type=int, default=None)
    ap.add_argument("--in-flight-max", type=int, default=None)
    ap.add_argument("--retries", type=int, default=2)
    ap.add_argument("--straggler-factor", type=float, default=None)
    ap.add_argument("--park-factor", type=float, default=None)
    ap.add_argument("--no-publish", action="store_true",
                    help="do not train/publish missing model artifacts")
    ap.add_argument("--gc-keep-hardware", default=None,
                    help="comma-separated hardware keys to KEEP on "
                    "periodic store GC (default: GC disabled)")
    ap.add_argument("--gc-every", type=float, default=60.0,
                    help="pool-seconds between GC passes")
    ap.add_argument("--journal", default=None,
                    help="write-ahead request journal path (default: "
                    "<store-dir>/journal.jsonl when using a store dir)")
    ap.add_argument("--no-journal", action="store_true",
                    help="disable the request journal entirely")
    ap.add_argument("--fsync", default="batch",
                    choices=("always", "batch", "off"),
                    help="journal durability mode: 'always' fsyncs every "
                    "record inline, 'batch' (default) group-commits — "
                    "acks still wait for the fsync covering their "
                    "records, but one flush covers a whole burst — "
                    "'off' never fsyncs (machine-crash unsafe, "
                    "process-kill safe)")
    ap.add_argument("--recover", action="store_true",
                    help="replay the journal on startup: restore "
                    "resolved requests, resubmit interrupted ones with "
                    "their remaining budget, restore tenant spend")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    from repro.launch.fleet import build_pool
    from repro.launch.signals import install_drain_handlers
    from repro.service import ShardedConfigStore, TuningDaemon
    from repro.service.journal import RequestJournal
    from repro.service.tenants import TenantManager
    from repro.tuning import ConfigStore

    import os
    if args.store is not None:
        store = ConfigStore(args.store)
        store_root = os.path.dirname(os.path.abspath(args.store))
    else:
        store_root = args.store_dir or "tuning_corpus"
        store = ShardedConfigStore(store_root, n_shards=args.shards)
    journal = None
    if not args.no_journal:
        journal = RequestJournal(
            args.journal or os.path.join(store_root, "journal.jsonl"),
            mode=args.fsync)
    if args.recover and journal is None:
        ap.error("--recover requires a journal (drop --no-journal)")
    pool = build_pool(args.backend, args.workers)
    gc_keep = None
    if args.gc_keep_hardware:
        gc_keep = {"keep_hardware": [h.strip() for h in
                                     args.gc_keep_hardware.split(",")
                                     if h.strip()]}
    daemon = TuningDaemon(
        pool, store, host=args.host, port=args.port,
        tenants=TenantManager(
            max_tenants=args.max_tenants,
            max_active_per_tenant=args.max_active_per_tenant,
            max_queued_per_tenant=args.max_queued_per_tenant,
            default_budget_s=args.default_tenant_budget),
        default_trial_budget=args.budget,
        max_active_jobs=args.max_active_jobs,
        gc_keep=gc_keep, gc_every_s=args.gc_every,
        journal=journal, recover=args.recover,
        verbose=args.verbose,
        in_flight=args.in_flight, in_flight_max=args.in_flight_max,
        retries=args.retries, straggler_factor=args.straggler_factor,
        park_factor=args.park_factor,
        publish_models=not args.no_publish)
    host, port = daemon.start()
    if daemon.recovery is not None:
        print(f"[daemon] recovered: {json.dumps(daemon.recovery)}",
              flush=True)
    print(f"[daemon] tuning service on {host}:{port} "
          f"({args.backend} backend, {pool.workers} workers, "
          f"store={store.path})", flush=True)
    install_drain_handlers(daemon.shutdown)
    try:
        daemon.wait()
    finally:
        pool.close()
    if daemon.final_report is not None:
        rep = daemon.final_report
        print(f"[daemon] drained: {len(rep.results)} jobs, "
              f"{rep.busy:.3f} worker-seconds on the pool clock")
    print(json.dumps({"tenants": daemon.tenants.snapshot()}, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
