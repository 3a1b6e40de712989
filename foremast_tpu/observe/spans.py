"""Dapper-style span pipeline: one correlation ID per judgment, end to end.

The reference brain's only observability is its re-published output
gauges (SURVEY §2.3) — when a judgment is late there is no way to see
*where* the tick spent its time. This module threads a trace through
service → store → worker tick stages → engine → controller and exports
it three ways:

  * Prometheus ``foremast_tick_stage_seconds{stage=...}`` histograms —
    per-stage latency attribution for every tick (always on when a
    Tracer is wired; the per-span cost is one perf_counter pair and a
    histogram observe);
  * a bounded ring buffer of Chrome-trace events, dumped as JSONL that
    Perfetto loads directly — gated by ``FOREMAST_TRACE_DIR`` (or an
    explicit ``trace_dir``), so the deployed default pays nothing for
    the buffer;
  * trace/span IDs injected into the JSON log records
    (``observe.logs.JsonFormatter``) so logs, metrics and traces all
    correlate on one ID.

Design: a single contextvar carries ``(tracer, active_span)``. Library
code (store, engine, arena) calls the module-level :func:`span` helper,
which attaches a child span to whatever tracer the caller's tick opened
— or no-ops when none is active. Only the process entry points (worker
loop, service app, controller) hold a Tracer instance, so the engine
never needs plumbing and un-instrumented callers pay one contextvar
read per call site.

Host spans around device work pass ``device=True``, which additionally
wraps the region in ``jax.profiler.TraceAnnotation`` — always, so in any
profiler session (``python3 -m chipbench.run --trace 1`` takes one) host
spans sit beside the XLA device ops on one timeline.

The worker tick's stage spans (``TICK_STAGES``) close the tick's span
tree: on each of a sweep's threads (prefetch, tick, writer) the stage
spans are SIBLINGS that never nest, so ``foremast_tick_stage_seconds``
sums are self time per thread. They open per slice, per dispatch group
or per wait — never per document. The one exception is stage ``gc``: a
generation-2 garbage collection, recorded as a span ``python.gc`` on
whatever thread ran it (it stops every Python thread), nested inside
whatever span it interrupted.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import gc
import json
import logging
import os
import random
import threading
import time
import weakref

log = logging.getLogger("foremast_tpu.observe.spans")

# (tracer, span) of the innermost open span. One var, not two: the
# module-level span() helper must attach children to the SAME tracer
# that opened the enclosing root, never to some other instance.
_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "foremast_active_span", default=None
)

# Stage-histogram buckets: warm columnar stages sit in the 100 us - 10 ms
# band while a fleet-cold fit runs tens of seconds; the default
# prometheus buckets would collapse the warm path into one bucket.
STAGE_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)

# The canonical tick stages, in tick order (docs/observability.md has the
# table: stage, span names, thread, attrs). `wait` is the tick thread
# blocked on the sweep pipeline's other threads; `housekeeping` is the
# sweep's head, slice boundaries and tail (and the tracer's own
# `trace.flush`). `gc` is the one stage that nests: a generation-2
# collection's seconds are also inside the span it interrupted. Kept here
# so the metrics lint and the docs can't drift from the emitters.
TICK_STAGES = (
    "claim",
    "admit",
    "metric_fetch",
    "pack",
    "fit",
    "arena_assemble",
    "h2d",
    "score",
    "decode",
    "decide",
    "write_back",
    "wait",
    "housekeeping",
    "gc",
)


# epoch offset of the monotonic clock, taken once at import
_CLOCK_ANCHOR = time.time() - time.perf_counter()


def clock() -> float:
    """Now, on the span timeline: the import-time wall-clock anchor plus
    the monotonic clock. Whoever stamps a marker that has to line up
    with span timestamps (a profiler sync annotation) stamps THIS, not
    `time.time()`: the two drift apart by whatever the wall clock was
    slewed or stepped since import."""
    return _CLOCK_ANCHOR + time.perf_counter()


# Span and trace IDs come from a per-process PRNG seeded once from the
# OS, not from uuid4(): that reads os.urandom on every call, a syscall
# made with the GIL released, and on a host whose syscalls take
# microseconds another busy Python thread (the sweep's prefetch stage)
# then takes the GIL and keeps it for a whole switch interval. Measured
# on the benchmark's TPU host: 5.4 ms a span under contention against
# 0.6 us for getrandbits (PERF.md section 6, PR 24).
_ids = random.Random()
os.register_at_fork(after_in_child=_ids.seed)  # a forked child re-seeds


def new_trace_id() -> str:
    """Mint a correlation ID in the span-pipeline format (16 hex
    digits). Public so callers that stamp IDs without an active span
    (the service's tracing-off path) stay format-compatible with
    span-derived ones."""
    return f"{_ids.getrandbits(64):016x}"


_new_id = new_trace_id


class Span:
    """One timed region. Completed spans are exported as Chrome trace
    events (phase "X": complete event with ts+dur in microseconds)."""

    __slots__ = (
        "trace_id",
        "span_id",
        "parent_id",
        "name",
        "stage",
        "attrs",
        "ts",
        "duration",
        "_t0",
    )

    def __init__(self, name, trace_id, parent_id, stage=None, attrs=None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.stage = stage
        self.attrs = attrs or {}
        self.duration = 0.0
        # wall-clock ts derived from ONE anchor + the monotonic clock:
        # if NTP steps the wall clock mid-tick, per-span time.time()
        # would shift later spans past/before their parent on the
        # Perfetto timeline while durations stay monotonic
        self.ts = clock()
        self._t0 = self.ts - _CLOCK_ANCHOR

    def to_event(self) -> dict:
        args = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
        }
        if self.stage:
            args["stage"] = self.stage
        args.update(self.attrs)
        return {
            "name": self.name,
            "cat": "foremast",
            "ph": "X",
            "ts": round(self.ts * 1e6, 1),
            "dur": round(self.duration * 1e6, 1),
            "pid": os.getpid(),
            # Perfetto wants a numeric tid; mask to keep it in range
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "args": args,
        }


class SpanRing:
    """Thread-safe bounded buffer of completed-span trace events.

    A deque(maxlen=N) ring: the newest `capacity` spans win, older ones
    fall off — a long-lived worker keeps the recent past resident for a
    /debug dump without unbounded growth. `total` counts everything ever
    added so a dump can report how much history scrolled away.
    """

    def __init__(self, capacity: int = 8192):
        self.capacity = capacity
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.total = 0

    def add(self, event: dict) -> None:
        with self._lock:
            self._events.append(event)
            self.total += 1

    def snapshot(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def dump_jsonl(self, path: str) -> int:
        """Write one Chrome trace event per line (Perfetto's JSON
        importer accepts newline-delimited events); returns #events.
        Written to a sibling temp file and renamed, so a reader never
        loads a half-written dump."""
        events = self.snapshot()
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            for e in events:
                f.write(json.dumps(e) + "\n")
        os.replace(tmp, path)
        return len(events)


@contextlib.contextmanager
def _null_span():
    yield None


@contextlib.contextmanager
def _annotation_only(name: str):
    """The `jax.profiler.TraceAnnotation` of a `device=True` span (alone,
    when no tracer is wired). Yields None like `_null_span`, so
    `with span(...) as s` is a Span or None at every call site."""
    try:
        import jax

        cm = jax.profiler.TraceAnnotation(name)
    except Exception:  # noqa: BLE001 - tracing must never break scoring
        cm = _null_span()
    with cm:
        yield None


class Tracer:
    """Per-process span factory + exporters.

    One Tracer per entry point (worker / service / controller). Opening
    a span publishes it as the context's active span, so nested
    module-level :func:`span` calls — engine, arena, store — parent to
    it automatically and share its trace ID.
    """

    # flush the ring to disk at most this often (root-span exits only)
    AUTOFLUSH_SECONDS = 10.0

    def __init__(
        self,
        service: str = "foremast",
        registry=None,
        trace_dir: str | None = None,
        buffer_size: int = 8192,
        histogram: bool = True,
    ):
        self.service = service
        self.trace_dir = (
            trace_dir
            if trace_dir is not None
            else (os.environ.get("FOREMAST_TRACE_DIR") or None)
        )
        self.ring = SpanRing(buffer_size) if self.trace_dir else None
        # stage -> seconds within the latest root span (tick/poll/
        # request); reset when a new root opens so the /debug/state
        # breakdown never mixes stages from different ticks
        self.last_stage_seconds: dict[str, float] = {}
        self._hist = None
        if histogram:
            from prometheus_client import Histogram

            # shared per (registry, name): two Tracers over one registry
            # (service app recreated, worker+controller embedded) must
            # reuse the family, not collide on prometheus_client's
            # duplicate-registration check
            self._hist = _shared_family(
                registry,
                "foremast_tick_stage_seconds",
                lambda reg: Histogram(
                    "foremast_tick_stage_seconds",
                    "duration of one judgment-tick stage",
                    ["stage"],
                    registry=reg,
                    buckets=STAGE_BUCKETS,
                ),
            )
        self._last_flush = time.monotonic()
        self._flush_lock = threading.Lock()
        self._flush_active = False
        self._flush_warned = False
        # serializes dump_jsonl between explicit flush() callers and the
        # background autoflush thread (both write the same target path)
        self._io_lock = threading.Lock()
        # events of the generation-2 collections the gc hook saw, put in
        # the ring at the next span's close (`_finish`): a collection can
        # stop a thread inside the ring's lock
        self._gc_done: collections.deque = collections.deque(maxlen=1024)
        # the `gc` series, made now: a run with no generation-2 collection
        # reads 0, not absent, and the hook observes it without `labels()`
        # and the family's lock
        self._gc_hist = self._hist.labels(stage="gc") if self._hist is not None else None
        _watch_gc(self)

    # -- span creation ---------------------------------------------------

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        stage: str | None = None,
        trace_id: str | None = None,
        device: bool = False,
        **attrs,
    ):
        """Open a span. Child of the context's active span unless an
        explicit `trace_id` is given (adopting a correlation ID carried
        by a request/document starts a fresh root under that ID).
        `device=True` wraps the region in jax.profiler.TraceAnnotation
        so it shows on the XLA timeline too."""
        parent = current_span()
        if trace_id is not None:
            s = Span(name, trace_id, "", stage=stage, attrs=attrs)
        elif parent is not None:
            s = Span(
                name, parent.trace_id, parent.span_id, stage=stage, attrs=attrs
            )
        else:
            s = Span(name, _new_id(), "", stage=stage, attrs=attrs)
        if parent is None:
            # fresh root: restart the stage breakdown (atomic swap, so a
            # concurrent /debug/state read sees old-or-new, never a mix)
            self.last_stage_seconds = {}
        token = _ACTIVE.set((self, s))
        try:
            with _annotation_only(name) if device else _null_span():
                yield s
        finally:
            s.duration = time.perf_counter() - s._t0
            _ACTIVE.reset(token)
            self._finish(s, root=parent is None)

    def _finish(self, s: Span, root: bool) -> None:
        if self._gc_done:
            self._record_gc()
        self._record(s)
        if root and self.ring is not None:
            self._autoflush()

    def _record(self, s: Span) -> None:
        if s.stage is not None:
            # accumulate: a tick may open several spans per stage (chunked
            # fetch/decide/write-back, per-bucket score) and the breakdown
            # must attribute ALL of that stage's time, not the last chunk's
            self.last_stage_seconds[s.stage] = (
                self.last_stage_seconds.get(s.stage, 0.0) + s.duration
            )
            if self._hist is not None:
                self._hist.labels(stage=s.stage).observe(s.duration)
        if self.ring is not None:
            self.ring.add(s.to_event())

    def _record_gc(self) -> None:
        while True:
            try:
                event = self._gc_done.popleft()
            except IndexError:
                return
            self.ring.add(event)

    def _autoflush(self) -> None:
        """Flush on a daemon thread: root-span exit runs on whatever
        thread (or event loop) closed the span, and serializing the
        whole ring there would stall it. At most one background flush
        at a time; a flush in flight just defers to the next root.
        The elapsed-time check sits under the lock too — an unlocked
        read of `_last_flush` raced concurrent root exits into
        duplicate flush threads (lock-discipline finding)."""
        with self._flush_lock:
            if self._flush_active:
                return
            if (
                time.monotonic() - self._last_flush
                < self.AUTOFLUSH_SECONDS
            ):
                return
            self._flush_active = True
            # stamp inside the lock so concurrent root exits don't pile
            # up more flush threads before the first one finishes
            self._last_flush = time.monotonic()

        def _run():
            # the dump serializes the whole ring under the GIL: a span of
            # its own, outside any tick's tree, names that time
            s = Span("trace.flush", _new_id(), "", stage="housekeeping")
            try:
                s.attrs["events"] = self._dump(self.trace_path())
            except Exception as e:  # noqa: BLE001 - tracing must never break serving
                # warn ONCE: an unwritable FOREMAST_TRACE_DIR otherwise
                # fails every 10 s with zero signal until shutdown
                if not self._flush_warned:
                    self._flush_warned = True
                    log.warning(
                        "trace flush to %s failed (%s); dumps disabled "
                        "until the path is writable",
                        self.trace_path(),
                        e,
                    )
            finally:
                s.duration = time.perf_counter() - s._t0
                self._record(s)
                with self._flush_lock:
                    self._flush_active = False

        threading.Thread(
            target=_run, name="foremast-trace-flush", daemon=True
        ).start()

    # -- export ----------------------------------------------------------

    def trace_path(self) -> str | None:
        if not self.trace_dir:
            return None
        return os.path.join(
            self.trace_dir,
            f"foremast-{self.service}-{os.getpid()}.trace.jsonl",
        )

    def flush(self, path: str | None = None) -> str | None:
        """Dump the ring buffer as Perfetto-loadable JSONL; returns the
        path written, or None when the buffer is disabled. Serialized
        against the background autoflush — both write the same target,
        and two unguarded writers would truncate each other's temp
        file."""
        if self.ring is None:
            return None
        target = path or self.trace_path()
        self._dump(target)
        return target

    def _dump(self, target: str) -> int:
        """Write the ring to `target`; -> the events written."""
        self._record_gc()
        with self._io_lock:
            os.makedirs(os.path.dirname(target) or ".", exist_ok=True)
            # _io_lock exists to serialize exactly this dump between
            # explicit flush() callers and the autoflush daemon — two
            # unguarded writers would truncate each other's temp file
            # foremast: ignore[blocking-under-lock]
            n = self.ring.dump_jsonl(target)
        # `_last_flush` is _flush_lock state (the autoflush scheduler's
        # elapsed check reads it there); stamping it under _io_lock
        # raced the two critical sections against each other
        # (thread-escape mixed-guard finding)
        with self._flush_lock:
            self._last_flush = time.monotonic()
        return n

    def debug_state(self) -> dict:
        return {
            "service": self.service,
            "trace_dir": self.trace_dir,
            "buffer_spans": len(self.ring) if self.ring is not None else 0,
            "spans_total": self.ring.total if self.ring is not None else 0,
            "last_stage_seconds": dict(self.last_stage_seconds),
        }


# ---------------------------------------------------------------------------
# generation-2 garbage collections as spans
# ---------------------------------------------------------------------------

# the newest Tracer, weakly: the one the gc hook records into (one worker,
# service or controller a process; a test's later Tracer takes over)
_gc_tracer = None
# (tracer, span) of the generation-2 collection in progress: collections
# never overlap, whatever thread runs them
_gc_open = None


def _watch_gc(tracer: Tracer) -> None:
    global _gc_tracer
    if _gc_tracer is None:
        gc.callbacks.append(_on_gc)
    _gc_tracer = weakref.ref(tracer)


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks` hook: a generation-2 collection becomes a span
    `python.gc` (stage `gc`) of the hook's tracer, on the thread that ran
    it and under whatever span is open there, if one is. Its seconds go
    to the stage at once, so a window's stage seconds hold the window's
    collections and no earlier one; its event waits for the next span's
    close, since the collection may have stopped this thread inside the
    ring's lock."""
    if info["generation"] != 2:
        return
    global _gc_open
    if phase == "start":
        tracer = _gc_tracer()
        if tracer is None:
            return
        parent = current_span()
        s = Span(
            "python.gc",
            parent.trace_id if parent is not None else _new_id(),
            parent.span_id if parent is not None else "",
            stage="gc",
            attrs={"generation": 2},
        )
        _gc_open = (tracer, s)
    elif _gc_open is not None:
        tracer, s = _gc_open
        _gc_open = None
        s.duration = time.perf_counter() - s._t0
        s.attrs.update(collected=info["collected"], uncollectable=info["uncollectable"])
        seconds = tracer.last_stage_seconds
        seconds["gc"] = seconds.get("gc", 0.0) + s.duration
        if tracer._gc_hist is not None:
            tracer._gc_hist.observe(s.duration)
        if tracer.ring is not None:
            tracer._gc_done.append(s.to_event())


# ---------------------------------------------------------------------------
# ambient helpers — what library code uses
# ---------------------------------------------------------------------------


def current_span() -> Span | None:
    """The innermost open span of this context (None outside any)."""
    active = _ACTIVE.get()
    return active[1] if active is not None else None


def span(name: str, stage: str | None = None, device: bool = False, **attrs):
    """Child span on the caller's ambient tracer, or a no-op when no
    tracer opened a span in this context — library code (store, engine,
    arena) instruments unconditionally and costs one contextvar read
    when tracing is off."""
    active = _ACTIVE.get()
    if active is None:
        return _annotation_only(name) if device else _null_span()
    return active[0].span(name, stage=stage, device=device, **attrs)


def note(s: "Span | None", **attrs) -> None:
    """Add counts known only at the END of a span's region (docs
    demoted, bytes handed to the device) to its attrs; a no-op on the
    None an un-traced `with span(...) as s` yields."""
    if s is not None:
        s.attrs.update(attrs)


def inherit_span(fn):
    """Wrap `fn` so it runs under the submitting thread's ambient span.
    ThreadPoolExecutor workers start with an empty context, so without
    this their log records lose the tick's trace_id/span_id — exactly
    the fetch-failure logs the correlation exists to join. A single
    shared `Context.run` cannot be entered concurrently, so only the
    active-span var is re-seated (and reset) per call."""
    active = _ACTIVE.get()

    def wrapped(*args, **kwargs):
        token = _ACTIVE.set(active)
        try:
            return fn(*args, **kwargs)
        finally:
            _ACTIVE.reset(token)

    return wrapped


# ---------------------------------------------------------------------------
# shared metric families (service requests, controller transitions,
# stage histograms, gauge-drop counters)
# ---------------------------------------------------------------------------

# one collector object per (registry, name): several make_app()/Tracer/
# controller instances over one registry must share the family, not
# collide on prometheus_client's duplicate-registration error
_FAMILIES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_FAMILY_LOCK = threading.Lock()


def _shared_family(registry, name: str, make):
    from prometheus_client import REGISTRY

    reg = registry if registry is not None else REGISTRY
    with _FAMILY_LOCK:
        per = _FAMILIES.get(reg)
        if per is None:
            per = {}
            _FAMILIES[reg] = per
        fam = per.get(name)
        if fam is None:
            fam = make(reg)
            per[name] = fam
        return fam


def counter(name: str, documentation: str, labels=(), registry=None):
    from prometheus_client import Counter

    return _shared_family(
        registry,
        name,
        lambda reg: Counter(name, documentation, list(labels), registry=reg),
    )


# ---------------------------------------------------------------------------
# /metrics + /healthz + /debug/state exposition server (worker side)
# ---------------------------------------------------------------------------


def start_observe_server(
    port: int,
    registry=None,
    state_fn=None,
    host: str = "0.0.0.0",
    max_port_tries: int = 1,
):
    """Serve /metrics (Prometheus exposition), /healthz, and
    /debug/state (JSON varz from `state_fn`) on one port — the worker's
    :8000 scrape endpoint, extended. Returns (server, thread); the
    thread is a daemon, same lifecycle as prometheus_client's
    start_http_server.

    `max_port_tries` > 1 auto-increments past ports already bound (up
    to port+tries-1): two workers on one host — the mesh's normal
    topology — must not kill each other over :8000. Read the ACTUAL
    port back from server.server_address (the mesh publishes it in the
    member record)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from prometheus_client import CONTENT_TYPE_LATEST, REGISTRY, generate_latest

    reg = registry if registry is not None else REGISTRY

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):  # metrics scrapes must not spam stderr
            pass

        def _send(self, code: int, body: bytes, ctype: str) -> None:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            path = self.path.split("?", 1)[0]
            if path == "/metrics":
                self._send(200, generate_latest(reg), CONTENT_TYPE_LATEST)
            elif path == "/healthz":
                from foremast_tpu import __version__

                body = json.dumps({"ok": True, "version": __version__})
                self._send(200, body.encode(), "application/json")
            elif path == "/debug/state":
                try:
                    state = state_fn() if state_fn is not None else {}
                    code = 200
                except Exception as e:  # noqa: BLE001 - varz must not 500-loop
                    state, code = {"error": str(e)}, 500
                body = json.dumps(state, default=str, indent=2)
                self._send(code, body.encode(), "application/json")
            else:
                self._send(404, b'{"reason": "not found"}', "application/json")

    import errno

    srv = None
    last_err: OSError | None = None
    # port 0 is the OS's ephemeral pick — auto-increment is meaningless
    tries = 1 if port == 0 else max(1, int(max_port_tries))
    for i in range(tries):
        try:
            srv = ThreadingHTTPServer((host, port + i), Handler)
            break
        except OSError as e:
            # only a BUSY port is worth walking past: privilege or
            # address errors repeat identically on port+1 and the
            # configured port must stay in the error the operator sees
            if e.errno != errno.EADDRINUSE:
                raise
            last_err = e
    if srv is None:
        raise last_err
    if port and srv.server_address[1] != port:
        logging.getLogger("foremast_tpu.observe").info(
            "observe port %d busy; serving /metrics + /debug/state on "
            ":%d instead", port, srv.server_address[1],
        )
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    return srv, thread
