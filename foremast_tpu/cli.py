"""Command-line surface: score / serve / worker / watch / unwatch / rules.

The reference drives its system with kubectl plus two tiny plugins
(`bin/kubectl-watch`, `bin/kubectl-unwatch` — merge-patching
DeploymentMonitor.spec.continuous, `bin/kubectl-watch:3`). This framework
adds a first-class CLI:

  score    one-shot health judgment of an ApplicationHealthAnalyzeRequest
           JSON (the minimum end-to-end slice: request -> windows -> batched
           TPU judgment -> reference wire-format response)
  serve    the REST job gateway on :8099 (foremast-service equivalent)
  worker   the scoring worker loop + :8000 gauge exposition (brain
           equivalent)
  watch    / unwatch — toggle continuous monitoring on a DeploymentMonitor
           (kubectl-watch parity, via the API server)
  rules    print the generated PrometheusRule recording-rules manifest

`python -m foremast_tpu <cmd>` and the `bin/foremast` shim both land here.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time


def _add_score(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "score", help="one-shot health judgment of a request JSON"
    )
    p.set_defaults(fn=cmd_score)
    p.add_argument(
        "--request",
        required=True,
        help="path to ApplicationHealthAnalyzeRequest JSON ('-' for stdin)",
    )
    p.add_argument(
        "--current",
        action="append",
        default=[],
        metavar="ALIAS=CSV",
        help="replay trace for the current window of ALIAS",
    )
    p.add_argument("--baseline", action="append", default=[], metavar="ALIAS=CSV")
    p.add_argument("--historical", action="append", default=[], metavar="ALIAS=CSV")
    p.add_argument(
        "--prometheus",
        action="store_true",
        help="fetch real query_range URLs instead of replay traces",
    )
    p.add_argument(
        "--follow",
        action="store_true",
        help="keep re-checking until the job reaches a terminal status "
        "(the reference's incremental re-check loop); default judges once "
        "and finalizes",
    )
    p.add_argument("--poll", type=float, default=5.0, help="--follow poll seconds")


def _parse_assignments(pairs: list[str], flag: str) -> dict[str, str]:
    out = {}
    for pair in pairs:
        alias, sep, path = pair.partition("=")
        if not sep or not alias or not path:
            raise SystemExit(f"{flag} expects ALIAS=CSV, got {pair!r}")
        out[alias] = path
    return out


def cmd_score(args: argparse.Namespace) -> int:
    from foremast_tpu.jobs.convert import request_to_document
    from foremast_tpu.jobs.models import AnalyzeRequest, document_response
    from foremast_tpu.jobs.store import InMemoryStore, parse_time
    from foremast_tpu.jobs.worker import BrainWorker
    from urllib.parse import unquote

    from foremast_tpu.metrics.promql import decode_config
    from foremast_tpu.metrics.source import PrometheusSource, ReplaySource

    raw = sys.stdin.read() if args.request == "-" else open(args.request).read()
    req = AnalyzeRequest.from_json(json.loads(raw))
    doc = request_to_document(req)

    if args.prometheus:
        source = PrometheusSource()
    else:
        # replay traces are keyed by exact query URL: current/baseline/
        # historical configs for the same alias differ only in their URLs,
        # so route each category's URL to its own trace
        source = ReplaySource()
        for flag, config in (
            ("--current", doc.current_config),
            ("--baseline", doc.baseline_config),
            ("--historical", doc.historical_config),
        ):
            assignments = _parse_assignments(getattr(args, flag[2:]), flag)
            urls = decode_config(config)
            for alias, path in assignments.items():
                if alias not in urls:
                    raise SystemExit(
                        f"{flag} {alias}: no such alias in the request's "
                        f"{flag[2:]} metrics (have: {sorted(urls) or 'none'})"
                    )
                # ReplaySource matches patterns against the *unquoted* URL
                source.register_csv(unquote(urls[alias]), path)

    store = InMemoryStore()
    doc, _ = store.create(doc)
    # same env-var config surface as the worker loop (the reference brain
    # is configured entirely through env, foremast-brain/README.md:20-38)
    from foremast_tpu.config import BrainConfig

    worker = BrainWorker(store, source, BrainConfig.from_env(), claim_limit=1)

    from foremast_tpu.jobs.models import (
        STATUS_COMPLETED_HEALTH,
        STATUS_COMPLETED_UNHEALTH,
        TERMINAL_STATUSES,
    )

    if args.follow:
        while store.get(doc.id).status not in TERMINAL_STATUSES:
            worker.tick()
            if store.get(doc.id).status in TERMINAL_STATUSES:
                break
            time.sleep(args.poll)
    else:
        # one-shot: clamp "now" past endTime so a healthy window finalizes
        end = parse_time(doc.end_time)
        worker.tick(now=max(time.time(), end + 1))

    final = store.get(doc.id)
    json.dump(document_response(final), sys.stdout, indent=2)
    print()
    # exit 0 only when the judgment actually evaluated the metrics
    # (healthy OR anomaly); preprocess_failed / abort / completed_unknown
    # mean no judgment was made, which must fail a CI gate.
    return (
        0
        if final.status in (STATUS_COMPLETED_HEALTH, STATUS_COMPLETED_UNHEALTH)
        else 1
    )


def _make_store(elastic_url: str | None, chaos=None, breaker=None, stop=None):
    """ES-backed store with the reference's connect-retry loop
    (service main.go:248-260), or in-memory when no URL is given.

    Falls back to the reference's env vars (`ELASTIC_URL` for the service,
    `ES_ENDPOINT` for the engine, main.go:236-243 / foremast-brain.yaml:22)
    so the deployed containers need no flags.

    The connect loop is bounded (ISSUE 9 satellite):
    `FOREMAST_ES_CONNECT_DEADLINE_SECONDS` (0/unset = the reference's
    forever-retry) turns a store that never comes up into a LOUD exit
    instead of an un-stoppable wait, and `stop` (shutdown signal) is
    honored between retries."""
    import os

    from foremast_tpu.jobs.store import ElasticsearchStore, InMemoryStore

    elastic_url = (
        elastic_url or os.environ.get("ELASTIC_URL") or os.environ.get("ES_ENDPOINT")
    )
    if not elastic_url:
        return InMemoryStore()
    store = ElasticsearchStore(elastic_url, chaos=chaos, breaker=breaker)
    deadline = float(
        os.environ.get("FOREMAST_ES_CONNECT_DEADLINE_SECONDS", "") or 0.0
    )
    if not store.wait_ready(max_wait=deadline or None, stop=stop):
        if stop and stop():
            # a SIGTERM during the connect loop is a GRACEFUL shutdown:
            # exit 0, or a rolling restart reads as a crash loop
            print(
                "shutdown requested during Elasticsearch connect; "
                "exiting cleanly",
                file=sys.stderr,
            )
            raise SystemExit(0)
        state = store.connect_state
        raise SystemExit(
            f"could not reach Elasticsearch at {elastic_url} within "
            f"{deadline:.0f}s ({state['attempts']} attempts, last error: "
            f"{state['last_error']}); set "
            "FOREMAST_ES_CONNECT_DEADLINE_SECONDS=0 to wait forever"
        )
    return store


def _mesh_member(store, worker_id: str, chaos_plan=None):
    """THE worker-mesh Membership + MeshRouter construction — shared by
    the single-worker branch and the mesh-of-pods leader (ISSUE 13), so
    the lease/replica/route-label env resolution and the chaos clock
    wiring can never drift between the two deployment modes."""
    import os

    from foremast_tpu.mesh import Membership, MeshRouter

    mesh_kw = {}
    if chaos_plan is not None:
        # chaos "clock" edge: skew rules shift the clock this member
        # stamps leases with AND reads peers' leases by (membership.py
        # documents the tolerance: renewal every lease/3 means a reader
        # surviving skew < 2/3 lease)
        mesh_kw["clock"] = chaos_plan.edge("clock").clock()
    membership = Membership(
        store,
        worker_id,
        lease_seconds=float(
            os.environ.get("FOREMAST_MESH_LEASE_SECONDS", "") or "15"
        ),
        **mesh_kw,
    )
    router = MeshRouter(
        membership,
        replicas=_env_int("FOREMAST_MESH_REPLICAS", 64),
        route_label=(
            os.environ.get("FOREMAST_MESH_ROUTE_LABEL", "") or "app"
        ),
    )
    return membership, router


def cmd_serve(args: argparse.Namespace) -> int:
    from foremast_tpu.observe import setup_logging
    from foremast_tpu.observe.spans import Tracer
    from foremast_tpu.service.app import serve

    setup_logging()
    store = _make_store(args.elastic_url)
    serve(
        host=args.host,
        port=args.port,
        store=store,
        query_endpoint=args.query_endpoint,
        # per-request spans + the /debug/state trace section; the ring
        # buffer dump is gated by FOREMAST_TRACE_DIR as everywhere
        tracer=Tracer(service="service"),
    )
    return 0


def _mount_ingest(
    inner, gauge_port: int, router=None, snapshot_dir=None,
    chaos=None, degrade=None, handoff=None, dirty=None,
):
    """FOREMAST_INGEST=1: wrap the pull source in the push-plane
    RingSource (docs/operations.md "Ingest plane") — warm fetches become
    resident ring gathers, cold misses fall back to `inner` and are
    backfilled so the next tick hits. Starts the remote-write receiver
    (FOREMAST_INGEST_PORT; 0 = direct push/backfill only; port 0 taken
    literally means ephemeral in mesh mode, where every co-hosted
    worker needs its own receiver) and registers the foremast_ingest_*
    families when a scrape port is live. `router` (mesh mode) makes the
    receiver answer pushes for series another member owns with that
    member's advertised address. `snapshot_dir` mounts the durable ring
    (docs/operations.md "Restarts and upgrades"): restore runs BEFORE
    the receiver accepts its first push, then live pushes journal.
    Returns (source, ring, receiver or None, snapshotter or None)."""
    from foremast_tpu.ingest import (
        IngestCollector,
        RingSource,
        RingStore,
        start_ingest_server,
    )

    ring = RingStore.from_env()
    snapshotter = None
    if snapshot_dir:
        from foremast_tpu.ingest import RingSnapshotter

        snapshotter = RingSnapshotter.from_env(ring, snapshot_dir)
        # restore() logs series/samples + the discard breakdown itself
        snapshotter.restore()
        snapshotter.attach()
    source = RingSource(ring, fallback=inner)
    # ring-first cold path (ISSUE 10): the worker reads historical
    # ranges straight off resident columns (hist_columns), admits
    # newcomers on short coverage, and refines provisional fits in the
    # background — say so at startup, with the two knobs that tune it.
    # Partial admission is pure-push only (source.hist_columns), so a
    # fallback-configured fleet is told its floor is inert.
    from foremast_tpu.jobs.refine import refine_docs_per_tick_from_env

    logging.getLogger("foremast_tpu.cli").info(
        "cold-start path: ring-resident historical reads ON "
        "(admit floor %.0f s%s — FOREMAST_ADMIT_MIN_COVERAGE_SECONDS; "
        "refinement %d docs/tick — FOREMAST_REFINE_DOCS_PER_TICK; "
        "docs/operations.md \"Cold start & churn\")",
        source.admit_floor,
        "" if inner is None else " [inert: fallback configured]",
        refine_docs_per_tick_from_env(),
    )
    if handoff is not None:
        # the handoff plane streams/applies THIS ring's series; the
        # manager exists before the ring (it needs the chaos edge and
        # the router's route label), so bind it here
        handoff.ring_store = ring
    port = _env_int("FOREMAST_INGEST_PORT", 9009)
    srv = None
    if port or router is not None:
        srv, _ = start_ingest_server(
            port, ring, book=source.book, router=router,
            chaos=chaos,
            degrade_stats=degrade.stats if degrade is not None else None,
            handoff=handoff,
            dirty=dirty,
        )
    if gauge_port:
        from prometheus_client import REGISTRY

        REGISTRY.register(
            IngestCollector(
                ring,
                book=source.book,
                # per-codec stage breakdown, live only when a receiver
                # is (ISSUE 18: the wire families come from the wire)
                wire=getattr(srv, "_foremast_wire_stats", None),
            )
        )
    return source, ring, srv, snapshotter


def _persistent_worker_id(snap_dir: str, minted: str) -> str:
    """Stable worker identity across restarts (``<snap_dir>/worker.id``):
    a restarted worker re-joins the mesh as the SAME member, so the hash
    ring does not move and the restored ring/fit state matches exactly
    the partition it reclaims. First boot persists the minted id."""
    from foremast_tpu.ingest.snapshot import atomic_write

    path = os.path.join(snap_dir, "worker.id")
    try:
        with open(path) as fh:
            wid = fh.read().strip()
        if wid:
            return wid
    except OSError:
        pass
    atomic_write(path, minted.encode())
    return minted


def cmd_worker(args: argparse.Namespace) -> int:
    # FIRST, before the imports below create any package lock (native
    # and observe.spans both make module-level locks at import time):
    # FOREMAST_LOCK_WITNESS=1 wraps threading.Lock/RLock to record real
    # acquisition order and verify it against the committed static lock
    # graph at exit — installing later would leave those locks raw and
    # their edges invisible to the witness
    from foremast_tpu.analysis.witness import install_from_env

    install_from_env()
    # FOREMAST_RECOMPILE_WITNESS=1: count actual XLA backend compiles
    # for the life of the worker and log the total at exit — the
    # zero-warm-recompile contract's runtime witness (installed before
    # the first dispatch so the cold compiles are attributed too)
    from foremast_tpu.analysis.recompile_witness import (
        install_from_env as install_recompile_witness,
    )

    install_recompile_witness()

    from foremast_tpu import native
    from foremast_tpu.config import BrainConfig
    from foremast_tpu.jobs.worker import BrainWorker
    from foremast_tpu.metrics.source import PrometheusSource
    from foremast_tpu.observe.gauges import BrainGauges, make_verdict_hook
    from foremast_tpu.observe.spans import Tracer, start_observe_server

    from foremast_tpu.observe import setup_logging

    setup_logging()  # structured JSON logs at INFO (operational events —
    # claims, warmup, checkpoint, takeovers — are info-level)
    from foremast_tpu.device import device_info, enable_compile_cache

    cache_dir = enable_compile_cache()  # before ANY jax computation below
    native.ensure_built()  # startup-time compile, never in the hot path
    config = BrainConfig.from_env()

    # chaos plane + degradation bundle (ISSUE 9): FOREMAST_CHAOS_PLAN
    # unset (production) means chaos_plan is None and every injection
    # seam below receives None — a plain attribute check, no other
    # cost. The Degradation bundle (breakers, write-behind, tick
    # budget) is ALWAYS on: degrading through a real outage must not
    # require having opted into chaos testing.
    from foremast_tpu.chaos import Degradation, chaos_from_env

    chaos_plan = chaos_from_env()

    def _edge(name: str):
        return chaos_plan.edge(name) if chaos_plan is not None else None

    degrade = Degradation.from_env(
        max_stuck_seconds=config.max_stuck_seconds, chaos_plan=chaos_plan
    )

    # graceful shutdown flag, installed BEFORE the store connect loop so
    # a SIGTERM during an ES outage at startup stops the retry promptly
    # (wait_ready polls `stop` between sliced sleeps) instead of dying
    # on the default disposition; the worker loop reuses the same event
    import signal
    import threading

    stop_event = threading.Event()
    try:
        signal.signal(signal.SIGTERM, lambda s, f: stop_event.set())
        signal.signal(signal.SIGINT, lambda s, f: stop_event.set())
    except ValueError:
        pass  # not the main thread (embedded use); rely on the caller

    from foremast_tpu.engine.multivariate import MultivariateJudge

    univariate = None
    pod_mode = False
    if args.sharded:
        from foremast_tpu.parallel import init_distributed, make_global_mesh
        from foremast_tpu.parallel.batch import sharded_univariate

        # MUST run before any jax computation — including an orbax restore
        init_distributed()  # no-op single-host; JAX_COORDINATOR_* envs for pods
        univariate = sharded_univariate(config, mesh=make_global_mesh())
        import jax as _jax_sh

        pod_mode = _jax_sh.process_count() > 1
    else:
        # single-process worker: the judge spans the local device mesh
        # by default (ISSUE 13, FOREMAST_DEVICE_MESH — "auto" = all
        # local devices; a stock 1-device host resolves to None and
        # keeps the plain single-device judge). ONE shared resolver
        # with BrainWorker's device_mesh="env" path — the rules must
        # never drift between CLI and library construction.
        from foremast_tpu.parallel.batch import sharded_univariate

        univariate = sharded_univariate(config)
    judge = MultivariateJudge(config, univariate=univariate)
    # one line saying what this process actually got: a TPU pod that
    # silently came up on CPU must be visible in its first log lines
    # (after the judge exists, so --sharded's init_distributed has run)
    dev = device_info()
    logging.getLogger("foremast_tpu.cli").info(
        "worker backend: platform=%s device_kind=%s devices=%d mesh=%s "
        "compile_cache=%s",
        dev["platform"], dev["device_kind"], dev["device_count"],
        dict(univariate.mesh.shape) if univariate is not None else None,
        cache_dir,
    )

    if pod_mode:
        # followers never dial ES/Prometheus: only the leader needs
        # credentials and reachability (docs/operations.md pod mode)
        import jax as _jax_pm

        store = (
            _make_store(
                args.elastic_url,
                chaos=_edge("store"),
                breaker=degrade.breakers.get("store"),
                stop=stop_event.is_set,
            )
            if _jax_pm.process_index() == 0
            else None
        )
    else:
        store = _make_store(
            args.elastic_url,
            chaos=_edge("store"),
            breaker=degrade.breakers.get("store"),
            stop=stop_event.is_set,
        )

    ckpt_path = None
    ckpt_save = None
    if args.model_cache_dir:
        import os as _os

        import jax as _jax

        if _jax.process_count() > 1:
            # Pod mode (VERDICT r4 #1): the determinism contract
            # (parallel/distributed.py) requires IDENTICAL caches on
            # every process — a host restoring fewer fits than its peers
            # would route docs down different code paths and desync the
            # SPMD program. So only the LEADER touches disk (host-local
            # pickle — orbax's save is itself a cross-process collective
            # and would barrier-deadlock), the restore decision and the
            # restored entries are broadcast to every process, and
            # follower saves are no-ops.
            from foremast_tpu.parallel import broadcast_obj

            leader_pm = _jax.process_index() == 0
            ckpt_path = _os.path.abspath(
                _os.path.join(args.model_cache_dir, "model_cache.pod")
            )
            ckpt_save = (
                judge.cache.save_local if leader_pm else (lambda path: None)
            )
            restored = None
            if leader_pm and _os.path.exists(ckpt_path):
                try:
                    judge.cache.load_local(ckpt_path)
                    restored = judge.cache.snapshot()
                except Exception as e:  # noqa: BLE001 - stale/corrupt
                    print(
                        f"model-cache restore failed ({e}); starting cold",
                        file=sys.stderr,
                    )
            items = broadcast_obj(restored)
            if items:
                if not leader_pm:
                    judge.cache.put_many(items.items())
                print(
                    f"restored {len(items)} cached models pod-wide from "
                    f"{ckpt_path}",
                    file=sys.stderr,
                )
        else:
            import ast

            ckpt_path = _os.path.abspath(
                _os.path.join(args.model_cache_dir, "model_cache")
            )
            ckpt_save = judge.cache.save

            if _os.path.exists(ckpt_path):
                try:
                    n = judge.cache.load(
                        ckpt_path, key_parser=ast.literal_eval
                    )
                    print(
                        f"restored {n} cached models from {ckpt_path}",
                        file=sys.stderr,
                    )
                except Exception as e:  # noqa: BLE001 - stale/corrupt
                    print(
                        f"model-cache restore failed ({e}); starting cold",
                        file=sys.stderr,
                    )

    on_verdict = None
    worker_metrics = None
    tracer = None
    # pod mode: telemetry is leader-only — every process executes the
    # full tick over the IDENTICAL broadcast fleet, so follower gauges
    # would multiply all job/verdict/arena counts by process_count
    leader = store is not None if pod_mode else True
    if leader:
        # span pipeline: stage histograms always; the Perfetto ring
        # buffer only when FOREMAST_TRACE_DIR points somewhere
        tracer = Tracer(service="worker", trace_dir=config.trace_dir)
    if args.gauge_port and leader:
        from foremast_tpu.observe.gauges import WorkerMetrics

        gauges = BrainGauges()
        worker_metrics = WorkerMetrics()
        on_verdict = make_verdict_hook(gauges)
    # push-based ingest plane (opt-in): the ring + receiver live where
    # the fetches happen — the single worker, or the pod leader (the
    # only process whose LeaderSource.inner is real; follower fetches
    # stay leader-broadcast collectives, semantics unchanged)
    ingest_on = os.environ.get("FOREMAST_INGEST", "0") == "1"
    # worker mesh (opt-in): this worker takes a membership lease in the
    # job store and claims only its consistent-hash partition of the
    # fleet (docs/operations.md "Worker mesh"). Pod mode is already ONE
    # logical worker spanning processes — mesh partitioning happens
    # BETWEEN pods/workers, so a pod's followers never see it and a
    # leader could in principle join; wiring that is future work.
    mesh_on = os.environ.get("FOREMAST_MESH", "0") == "1"
    mesh_node = None
    ingest_srv = None
    # reactive plane (opt-in, ISSUE 12): FOREMAST_MICROTICK_SECONDS > 0
    # turns pushed-sample arrivals into micro-ticks — the receiver
    # marks each push's route key dirty and the worker judges just
    # those documents between full sweeps (docs/operations.md
    # "Event-driven detection"). Needs the ingest receiver: arrivals
    # are what the receiver sees.
    from foremast_tpu.reactive import microtick_seconds_from_env

    micro_seconds = microtick_seconds_from_env()
    dirty = None
    # durable data plane (opt-in): ring snapshots + append logs, fit
    # journals, and the persistent mesh identity all under one directory
    # (docs/operations.md "Restarts and upgrades")
    snap_dir = os.environ.get("FOREMAST_SNAPSHOT_DIR") or None
    snapshotter = None
    if mesh_on and pod_mode:
        # mesh-of-pods (ISSUE 13): each worker-mesh member is one POD —
        # a PodWorker whose device program spans its hosts' chips. Only
        # the LEADER holds the membership lease and evaluates the claim
        # filter (it is the only process with a real store); the
        # filtered claim set broadcasts to the followers exactly like
        # any other claim, so partitioning never shapes follower
        # control flow. Handoff/ingest stay leader-local pull-mode in
        # pods (the transfer plane needs a receiver per member —
        # docs/operations.md "Device mesh").
        print(
            "mesh-of-pods: this pod joins the worker mesh as ONE "
            "member (leader-held lease + claim filter)",
            file=sys.stderr,
        )
    if micro_seconds > 0 and pod_mode:
        # pod ticks are SPMD-broadcast collectives; a leader-local
        # micro-tick would desync followers — wiring micro-ticks
        # through the broadcast is future work
        print(
            "FOREMAST_MICROTICK_SECONDS ignored in pod mode "
            "(micro-ticks are single-worker; pod ticks are broadcast "
            "collectives)",
            file=sys.stderr,
        )
        micro_seconds = 0.0
    if micro_seconds > 0 and not ingest_on:
        print(
            "FOREMAST_MICROTICK_SECONDS needs FOREMAST_INGEST=1 (the "
            "receiver is what marks arrivals); staying tick-paced",
            file=sys.stderr,
        )
        micro_seconds = 0.0
    if snap_dir and pod_mode:
        # pod mode's determinism contract (identical caches on every
        # process, leader-only I/O) already has its own durability path
        # (--model-cache-dir leader checkpoint + broadcast); wiring the
        # journals through the broadcast is future work
        print(
            "FOREMAST_SNAPSHOT_DIR ignored in pod mode (use "
            "--model-cache-dir: leader checkpoint + broadcast)",
            file=sys.stderr,
        )
        snap_dir = None
    if pod_mode:
        # One logical worker spanning the jax.distributed cluster: the
        # leader claims/fetches/writes, everything is broadcast, the
        # judgment runs SPMD over the global mesh. Plain BrainWorkers
        # must NOT share a global mesh — each would claim different
        # docs into one SPMD program (docs/operations.md runbook).
        from foremast_tpu.parallel import LeaderSource, LeaderStore, PodWorker

        pod_inner = (
            PrometheusSource(
                chaos=_edge("prometheus"),
                breaker=degrade.breakers.get("prometheus"),
            )
            if store is not None
            else None
        )
        if ingest_on and pod_inner is not None:
            pod_inner, _pod_ring, ingest_srv, _ = _mount_ingest(
                pod_inner, args.gauge_port,
                chaos=_edge("receiver"), degrade=degrade,
            )
        pod_worker_id = None
        if mesh_on and store is not None:
            # the leader's seat in the worker mesh (mesh-of-pods): the
            # membership record and the claim stamps share one id, and
            # the MeshNode's claim_filter rides LeaderStore.claim so
            # the whole pod ticks over this member's partition only
            import uuid as _uuid

            from foremast_tpu.mesh import MeshNode

            pod_worker_id = f"pod-{_uuid.uuid4().hex[:8]}"
            pod_membership, pod_router = _mesh_member(
                store, pod_worker_id, chaos_plan
            )
            mesh_node = MeshNode(pod_membership, pod_router)
            mesh_node.start()
        worker = PodWorker(
            LeaderStore(store),
            LeaderSource(pod_inner),
            config=config,
            judge=judge,
            claim_limit=args.claim_limit,
            on_verdict=on_verdict,
            metrics=worker_metrics,
            tracer=tracer,
            mesh=mesh_node,
            degrade=degrade,
            **({"worker_id": pod_worker_id} if pod_worker_id else {}),
        )
    else:
        # mesh identity is minted HERE so the membership record and the
        # claim's processing_content stamp agree on one worker id; with
        # a snapshot dir the id PERSISTS, so a restart re-takes the same
        # mesh seat (no rebalance) and reclaims exactly the partition
        # its restored ring/fit state belongs to
        import uuid as _uuid

        worker_id = f"brain-{_uuid.uuid4().hex[:8]}"
        snap_lock = None
        if snap_dir:
            # exclusivity: two live workers sharing one snapshot dir
            # would interleave torn frames into the same shard logs and
            # join the mesh as ONE member. flock dies with the process
            # (SIGKILL included), so restarts acquire immediately; only
            # a genuinely concurrent second worker is refused — it runs
            # ephemeral rather than corrupting the first one's state.
            from foremast_tpu.ingest import lock_snapshot_dir

            snap_lock = lock_snapshot_dir(snap_dir)
            if snap_lock is None:
                print(
                    f"FOREMAST_SNAPSHOT_DIR {snap_dir} is held by "
                    "another live worker; running ephemeral (give "
                    "each co-hosted worker its own directory)",
                    file=sys.stderr,
                )
                snap_dir = None
        if snap_dir:
            worker_id = _persistent_worker_id(snap_dir, worker_id)
        membership = router = None
        if mesh_on:
            membership, router = _mesh_member(store, worker_id, chaos_plan)
        # planned handoff (ISSUE 11): rebalance on planned scale events
        # becomes a state TRANSFER — the joiner fences until the current
        # owners stream it its partition, SIGTERM drains instead of
        # abandoning state (docs/operations.md "Elastic scaling")
        handoff = None
        # ingest gates the plane: without a receiver there is no
        # transfer endpoint anywhere in the fleet — a fenced joiner
        # would idle out its whole deadline with nothing to receive,
        # a pure regression over PR-6 immediate claiming
        if (
            mesh_on
            and ingest_on
            and os.environ.get("FOREMAST_HANDOFF", "1") == "1"
        ):
            from foremast_tpu.mesh import HandoffManager

            handoff = HandoffManager(
                route_label=router.route_label,
                chaos=_edge("transfer"),
                breaker=degrade.breakers.get("transfer"),
            )
        if micro_seconds > 0:
            # dirty routing respects partition ownership: with a mesh
            # router wired, pushes for series another member owns are
            # counted foreign and never marked (that member's own
            # receiver marks them)
            from foremast_tpu.reactive import DirtySet

            dirty = DirtySet.from_env(
                route_label=(
                    router.route_label if router is not None else "app"
                ),
                owns=(
                    router.owns_series if router is not None else None
                ),
            )
            from foremast_tpu.reactive.dirty import microtick_docs_from_env

            logging.getLogger("foremast_tpu.cli").info(
                "reactive plane ON: micro-ticks every %.3f s, %d dirty "
                "keys/tick, dirty-set cap %d "
                "(docs/operations.md \"Event-driven detection\")",
                micro_seconds, microtick_docs_from_env(),
                dirty.max_keys,
            )
        single_source = PrometheusSource(
            chaos=_edge("prometheus"),
            breaker=degrade.breakers.get("prometheus"),
        )
        single_ring = None
        if ingest_on:
            single_source, single_ring, ingest_srv, snapshotter = (
                _mount_ingest(
                    single_source, args.gauge_port, router=router,
                    snapshot_dir=snap_dir,
                    chaos=_edge("receiver"), degrade=degrade,
                    handoff=handoff, dirty=dirty,
                )
            )
        if mesh_on:
            from foremast_tpu.mesh import MeshNode

            if ingest_srv is not None:
                # advertise where pushers can actually reach the
                # receiver: FOREMAST_MESH_ADVERTISE (host or host:port)
                # wins, the bound port fills any gap
                import socket as _socket

                adv = os.environ.get("FOREMAST_MESH_ADVERTISE") or ""
                adv_host, _, adv_port = adv.partition(":")
                membership.ingest_address = "{}:{}".format(
                    adv_host or _socket.gethostname(),
                    adv_port or ingest_srv.server_address[1],
                )
            mesh_node = MeshNode(
                membership, router, ring_store=single_ring,
                handoff=handoff,
            )
            mesh_node.start()
        worker = BrainWorker(
            store,
            single_source,
            config=config,
            judge=judge,
            worker_id=worker_id,
            claim_limit=args.claim_limit,
            on_verdict=on_verdict,
            metrics=worker_metrics,
            tracer=tracer,
            mesh=mesh_node,
            degrade=degrade,
            dirty=dirty,
        )
        if worker._sweep_sliceable():
            logging.getLogger("foremast_tpu.cli").info(
                "sliced sweeps ON: %d-doc slices under the %d-doc "
                "claim, dirty-drain preemption at slice boundaries "
                "(FOREMAST_SWEEP_SLICE_DOCS; docs/operations.md "
                "\"Event-driven detection\")",
                worker.sweep_slice_docs, worker.claim_limit,
            )
        if snap_dir:
            # fit journals restore lazily (the first claim of each doc
            # rehydrates its fits, so admission passes with no history
            # re-fetch) and write through on fit completion; snapshot
            # cadence + compaction run inside the tick loop
            fit_restored = worker.enable_fit_persistence(snap_dir)
            if any(fit_restored.values()):
                print(
                    f"restored fit state {fit_restored} from {snap_dir}",
                    file=sys.stderr,
                )
            if snapshotter is not None:
                worker.attach_ring_snapshotter(snapshotter)
    if args.gauge_port and leader:
        # /metrics + /healthz + /debug/state on the scrape port (the
        # reference exposed /metrics only). Auto-increment past a busy
        # port: co-hosted mesh workers must not fight over :8000 — the
        # actual port lands in the member record below.
        obs_srv, _ = start_observe_server(
            args.gauge_port,
            state_fn=worker.debug_state,
            max_port_tries=32,
        )
        if mesh_node is not None:
            from foremast_tpu.mesh import MeshCollector
            from prometheus_client import REGISTRY as _REG

            _REG.register(MeshCollector(mesh_node))
            mesh_node.membership.observe_port = obs_srv.server_address[1]
            mesh_node.membership.renew(force=True)
        if snap_dir:
            from foremast_tpu.ingest import SnapshotCollector
            from prometheus_client import REGISTRY as _REG2

            _REG2.register(
                SnapshotCollector(
                    snapshotter, journals=worker._fit_journals.values()
                )
            )
        # chaos/degradation exposition rides the same scrape port:
        # breaker states, degraded-doc counters, injected-fault counts
        from foremast_tpu.chaos import ChaosCollector
        from prometheus_client import REGISTRY as _REG3

        _REG3.register(ChaosCollector(degrade))
        if dirty is not None:
            from foremast_tpu.reactive import ReactiveCollector
            from prometheus_client import REGISTRY as _REG4

            _REG4.register(ReactiveCollector(dirty))

    after_tick = None
    if ckpt_path:
        state = {"dirty": False}

        def after_tick(n, _state=state):
            # checkpoint when work happened, on the following idle tick —
            # so saves never add latency to a busy scoring cycle
            if n > 0:
                _state["dirty"] = True
            elif _state["dirty"]:
                ckpt_save(ckpt_path)
                _state["dirty"] = False

    # graceful shutdown: the SIGTERM/SIGINT handlers were installed
    # before the store connect loop (top of this function); from here
    # `stop_event` makes the worker finish the in-flight tick (claimed
    # docs get written back) instead of dying mid-judgment — abandoned
    # claims would otherwise wait out MAX_STUCK_IN_SECONDS
    if args.warmup:
        worker.warmup()

    stop_fn = stop_event.is_set
    if mesh_node is not None and mesh_node.handoff is not None:
        # planned shutdown (ISSUE 11): on the stop signal, stream this
        # partition to the post-drain owners on a side thread while
        # the loop KEEPS TICKING — a draining member claims and judges
        # its partition to the end, so no verdict waits out a slow or
        # blackholed transfer fenced behind this member's claim-ring
        # seat. The loop exits once the stream lands (or fails:
        # survivors cold-refit via the PR-6 path); the finally block's
        # drain() then only leaves.
        drain_thread_box: list = [None]

        def stop_fn() -> bool:
            if not stop_event.is_set():
                return False
            t = drain_thread_box[0]
            if t is None:
                t = threading.Thread(
                    target=mesh_node.stream_drain,
                    name="handoff-drain",
                    daemon=True,
                )
                drain_thread_box[0] = t
                t.start()
            return not t.is_alive()

    loop_failed = False
    try:
        worker.run(
            poll_seconds=args.poll,
            stop=stop_fn,
            after_tick=after_tick,
        )
    except BaseException:
        loop_failed = True
        raise
    finally:
        # run even when a tick raises: the persistent fetch/prefetch
        # pools must not linger to interpreter-exit join, and the cache
        # checkpoint + trace dump are worth keeping from a crashed loop.
        # After a loop failure each step is guarded so a cleanup error
        # (unwritable ckpt dir, say) can never mask the exception that
        # killed the loop; on a CLEAN shutdown a failed checkpoint
        # still raises — losing the fitted-model cache must exit loudly,
        # not as a warning under exit 0.
        try:
            worker.close()
        except Exception as e:  # noqa: BLE001 — cleanup must not mask
            logging.getLogger("foremast_tpu.cli").warning(
                "worker pool shutdown failed: %s", e
            )
        if mesh_node is not None:
            # planned shutdown: DRAIN when the handoff plane is wired —
            # the partition's ring series + fits streamed to the
            # post-drain owners under the tick loop (stop_fn above), so
            # drain() here normally just leaves and the survivors take
            # over warm (docs/operations.md "Elastic scaling");
            # otherwise leave FIRST so peers drop this member (and
            # start claiming its partition) without waiting out the
            # lease. Either way a failure degrades (survivors
            # cold-refit via stuck-claim takeover), never masks the
            # loop's own error.
            try:
                if mesh_node.handoff is not None and not loop_failed:
                    mesh_node.drain()
                else:
                    mesh_node.close()
            except Exception as e:  # noqa: BLE001 — cleanup must not mask
                logging.getLogger("foremast_tpu.cli").warning(
                    "mesh drain/leave failed: %s", e
                )
        if ingest_srv is not None:
            # bounded drain: in-flight pushes finish (or are abandoned
            # as daemon threads), the listen port frees immediately
            try:
                from foremast_tpu.ingest import stop_ingest_server

                stop_ingest_server(ingest_srv)
            except Exception as e:  # noqa: BLE001 — cleanup must not mask
                logging.getLogger("foremast_tpu.cli").warning(
                    "ingest receiver shutdown failed: %s", e
                )
        if snapshotter is not None:
            # one final pass AFTER the receiver drained (the last
            # pushes are in) so the restart replays a snapshot, not a
            # long log; then release the log handles
            try:
                snapshotter.snapshot()
                snapshotter.close()
            except Exception as e:  # noqa: BLE001 — cleanup must not mask
                logging.getLogger("foremast_tpu.cli").warning(
                    "final ring snapshot failed: %s", e
                )
        ckpt_error = None
        if ckpt_path and len(judge.cache):
            try:
                ckpt_save(ckpt_path)  # final checkpoint on the way out
            except Exception as e:  # noqa: BLE001 — see loop_failed gate
                if loop_failed:
                    logging.getLogger("foremast_tpu.cli").warning(
                        "final model-cache checkpoint failed: %s", e
                    )
                else:
                    # clean shutdown: losing the fitted-model cache must
                    # exit loudly — but only after the trace dump below
                    # gets its chance (deferred, not raised here)
                    ckpt_error = e
        if tracer is not None:
            try:
                tracer.flush()  # final Perfetto dump (no-op w/o trace dir)
            except Exception as e:  # noqa: BLE001 — cleanup must not mask
                # neither an unwritable trace dir nor a serialization
                # bug may turn a clean shutdown into a nonzero exit or
                # mask the loop/checkpoint error — the judgment work
                # already succeeded
                logging.getLogger("foremast_tpu.cli").warning(
                    "final trace flush failed: %s", e
                )
        if ckpt_error is not None:
            raise ckpt_error
    return 0


def _toggle_continuous(args: argparse.Namespace, value: bool) -> int:
    from foremast_tpu.watch.kubeapi import HttpKube, NotFound

    kube = HttpKube(base_url=args.api_server)
    try:
        # merge-patch only spec.continuous (what the reference plugin's
        # `kubectl patch --type=merge` does) so concurrent spec/status
        # writers are never reverted
        monitor = kube.patch_monitor(
            args.namespace, args.name, {"spec": {"continuous": value}}
        )
    except NotFound:
        print(f"deploymentmonitor {args.namespace}/{args.name} not found", file=sys.stderr)
        return 1
    verb = "watching" if value else "no longer watching"
    print(f"Foremast is {verb} application {args.name}")
    print(f"Job: {monitor.status.job_id}")
    return 0


def cmd_watch(args: argparse.Namespace) -> int:
    return _toggle_continuous(args, True)


def cmd_unwatch(args: argparse.Namespace) -> int:
    return _toggle_continuous(args, False)


def cmd_watch_plane(args: argparse.Namespace) -> int:
    """Run the deployed watch-plane controller (barrelman equivalent)."""
    import os

    from foremast_tpu.observe import setup_logging
    from foremast_tpu.observe.spans import Tracer
    from foremast_tpu.watch.kubeapi import HttpKube
    from foremast_tpu.watch.plane import WatchPlane

    setup_logging()
    # the controller's one dependency edge gets the same chaos seam +
    # breaker the worker's clients carry (ISSUE 9): a FOREMAST_CHAOS_PLAN
    # rule on edge "kube" injects here, and a dead API server fails
    # fast once the breaker opens instead of stalling every poll
    from foremast_tpu.chaos import Degradation, chaos_from_env

    chaos_plan = chaos_from_env()
    degrade = Degradation.from_env(chaos_plan=chaos_plan)
    kube = HttpKube(
        base_url=args.api_server,
        chaos=chaos_plan.edge("kube") if chaos_plan is not None else None,
        breaker=degrade.breakers.get("kube"),
    )
    plane = WatchPlane(
        kube,
        own_namespace=args.namespace or os.environ.get("NAMESPACE", "foremast"),
        tracer=Tracer(service="controller"),
    )
    if args.gauge_port:
        # the transition counter and poll-stage histogram register on
        # the default registry — without this server they'd be
        # unscrapeable in the only process that produces them
        from foremast_tpu.chaos import ChaosCollector
        from foremast_tpu.observe.spans import start_observe_server
        from prometheus_client import REGISTRY as _REG

        _REG.register(ChaosCollector(degrade))
        start_observe_server(args.gauge_port, state_fn=plane.debug_state)
    plane.run()
    return 0


def cmd_ui(args: argparse.Namespace) -> int:
    from foremast_tpu.ui.app import serve as serve_ui

    serve_ui(
        host=args.host,
        port=args.port,
        service_endpoint=args.service_endpoint,
        namespace=args.namespace,
        app_name=args.app,
        demo=args.demo,
    )
    return 0


def cmd_rules(args: argparse.Namespace) -> int:
    from foremast_tpu.metrics.rules import prometheus_rule_manifest, to_yaml

    sys.stdout.write(
        to_yaml(prometheus_rule_manifest(namespace=args.namespace))
    )
    return 0


def _env_int(name: str, default: int) -> int:
    """Env-var int with a warning (not a crash) on malformed values —
    build_parser runs for EVERY subcommand, so a bad env var must not
    break unrelated commands with a raw traceback."""
    # thin wrapper: every call site passes a literal, registered knob
    # (FOREMAST_CLAIM_LIMIT), so the dynamic read here stays enumerable
    raw = os.environ.get(name)  # foremast: ignore[env-contract]
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        print(f"ignoring malformed {name}={raw!r}; using {default}", file=sys.stderr)
        return default


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="foremast", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    sub = parser.add_subparsers(dest="command", required=True)

    _add_score(sub)
    # each parser carries its handler via set_defaults(fn=...) so a new
    # subcommand can never be registered without one

    p = sub.add_parser("serve", help="REST job gateway on :8099")
    p.set_defaults(fn=cmd_serve)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8099)
    p.add_argument(
        "--elastic-url",
        default=None,
        help="Elasticsearch backend (ELASTIC_URL parity); default in-memory",
    )
    p.add_argument(
        "--query-endpoint",
        default=None,
        help="upstream Prometheus for /api/v1 proxy (QUERY_SERVICE_ENDPOINT)",
    )

    p = sub.add_parser("worker", help="scoring worker loop (brain)")
    p.set_defaults(fn=cmd_worker)
    p.add_argument("--elastic-url", default=None)
    p.add_argument("--poll", type=float, default=5.0)
    p.add_argument(
        "--claim-limit",
        type=int,
        default=_env_int("FOREMAST_CLAIM_LIMIT", 256),
        help="jobs claimed per tick; the whole claim scores as ONE batched "
        "program, so fleet-scale limits amortize fixed dispatch cost "
        "(env FOREMAST_CLAIM_LIMIT)",
    )
    p.add_argument(
        "--sharded",
        action="store_true",
        help="score over the full device mesh (multi-host via "
        "JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/JAX_PROCESS_ID)",
    )
    p.add_argument(
        "--model-cache-dir",
        default=None,
        help="orbax-checkpoint trained models here (warm restart skips "
        "LSTM retraining); restored on startup",
    )
    p.add_argument(
        "--warmup",
        action="store_true",
        help="precompile the scoring programs for the canonical shapes "
        "(claim-limit batch, 7-day history) at startup instead of "
        "paying the XLA compiles inside the first real tick",
    )
    p.add_argument(
        "--gauge-port",
        type=int,
        default=8000,
        help="foremastbrain:* gauge exposition port (0 disables)",
    )

    for name, fn, helptext in (
        ("watch", cmd_watch, "enable continuous monitoring (kubectl-watch parity)"),
        ("unwatch", cmd_unwatch, "disable continuous monitoring"),
    ):
        p = sub.add_parser(name, help=helptext)
        p.set_defaults(fn=fn)
        p.add_argument("name", help="DeploymentMonitor name (the app)")
        p.add_argument("--namespace", "-n", default="default")
        p.add_argument(
            "--api-server", default=None, help="API server URL (default in-cluster)"
        )

    p = sub.add_parser(
        "watch-plane",
        help="K8s controller loop: deployment watcher + status poller + remediation",
    )
    p.set_defaults(fn=cmd_watch_plane)
    p.add_argument(
        "--api-server", default=None, help="API server URL (default in-cluster)"
    )
    p.add_argument(
        "--namespace",
        default=None,
        help="controller's own namespace (NAMESPACE downward-API parity)",
    )
    p.add_argument(
        "--gauge-port",
        type=int,
        default=0,
        help="controller metrics/varz exposition port (0 disables; pick a "
        "port distinct from the worker's :8000 when co-hosted)",
    )

    p = sub.add_parser("ui", help="dashboard on :8080 (foremast-browser parity)")
    p.set_defaults(fn=cmd_ui)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument(
        "--service-endpoint",
        default=None,
        help="job-gateway base URL (FOREMAST_SERVICE_ENDPOINT)",
    )
    p.add_argument("--namespace", default=None, help="charted namespace label")
    p.add_argument("--app", default=None, help="charted app label")
    p.add_argument(
        "--demo",
        action="store_true",
        help="serve synthetic series from this process (no Prometheus needed)",
    )

    p = sub.add_parser("rules", help="print recording-rules manifest YAML")
    p.set_defaults(fn=cmd_rules)
    p.add_argument("--namespace", default="monitoring")

    return parser


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
