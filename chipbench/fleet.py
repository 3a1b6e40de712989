"""The fleet a cell drives: documents, a procedural metric source and a
tap on the store.

One general generator reads the configuration's `fleet` groups and the
traffic mix's parameters. Nothing here is specific to a cell. The fleet
is an array of slots; a slot holds one service (uid) of one group (its
aliases give F, and the selector under `auto` follows from F). Histories
are drawn at fetch from (fleet_seed, uid); the current windows of a sweep
are drawn in bulk for all slots; spikes and the comparison's sample are
drawn from the seed too.

What the program gives here is the system under test only: the
`Document`/`InMemoryStore`/`MetricSource` interfaces it is driven by.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from chipbench import series
from foremast_tpu.jobs.models import (
    STATUS_PREPROCESS_COMPLETED,
    TERMINAL_STATUSES,
    Document,
)
from foremast_tpu.jobs.store import InMemoryStore
from foremast_tpu.metrics.source import MetricSource


class TapStore(InMemoryStore):
    """The in-process store with a tap on what ticks write to it."""

    def __init__(self):
        super().__init__()
        self.tap = None

    def update(self, doc):
        out = super().update(doc)
        if self.tap is not None:
            self.tap((doc,))
        return out

    def update_many(self, docs):
        super().update_many(docs)
        if self.tap is not None:
            self.tap(docs)


class ProceduralSource(MetricSource):
    """Serves a URL by drawing its series: O(1) lookup, no host copy of
    any history (73,728 x 4 x 10,080 points would be 12 GB)."""

    concurrent_fetch = False

    def __init__(self, fleet: "Fleet"):
        self.fleet = fleet
        self.index: dict[str, tuple] = {}
        self._hist: dict[int, np.ndarray] = {}

    def fetch(self, url: str):
        kind, slot, j = self.index[url]
        fl = self.fleet
        if kind == 0:
            return fl.cur_times, fl.cur_values[fl.group_of[slot]][fl.local[slot], j]
        if kind == 2:
            base = fl.base_values[fl.group_of[slot]][fl.local[slot], j]
            return fl.base_times[: len(base)], base
        uid = fl.uid[slot]
        block = self._hist.get(uid)
        if block is None:
            if len(self._hist) >= 8:
                self._hist.pop(next(iter(self._hist)))
            block = fl.history(slot)
            self._hist[uid] = block
        return fl.hist_times, block[j]


class Fleet:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        # the fleet's histories come from the configuration's fleet_seed,
        # so that the fitted fleet a checkout's first run persisted serves
        # every later run; what a window SENDS (current windows, spikes,
        # the sample) comes from --seed
        self.fleet_seed = int(cfg["fleet_seed"])
        self.fam = cfg["series"]
        self.n_hist = int(cfg["history_points"])
        self.w = int(cfg["window_points"])
        self.step = int(cfg["step_seconds"])
        self.groups = cfg["fleet"]
        self.group_of, self.local = series.slot_layout(self.groups)
        self.slots = len(self.group_of)
        self.nwin = np.array(
            [len(self.groups[g]["aliases"]) for g in self.group_of], np.int64
        )
        self.uid = np.arange(self.slots, dtype=np.int64)
        self.gen = np.zeros(self.slots, np.int64)
        # An AE's initial weights follow its service's index in the batch it
        # is fitted with (a fault of the program, PERF.md section 7), so the
        # reference has to be told it. The whole fleet is cold-fitted once, in
        # creation order, in slices of whole chunks: the index is the slot's
        # rank among the docs of its own kind in its chunk.
        slice_docs = int(cfg["env"]["FOREMAST_SWEEP_SLICE_DOCS"])
        chunk_docs = int(cfg["env"]["FOREMAST_COLD_CHUNK_DOCS"])
        if slice_docs % chunk_docs:
            raise SystemExit("FOREMAST_SWEEP_SLICE_DOCS has to hold whole cold chunks")
        self.fit_pos = series.fit_positions(self.groups, self.group_of, chunk_docs)
        epoch = int(cfg["epoch"])
        self.hist_times = epoch + self.step * np.arange(self.n_hist, dtype=np.int64)
        self.hist_end = int(self.hist_times[-1]) + self.step
        end = time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime(self.hist_end + 30 * 86_400)
        )
        self.end_time = end
        self.store = TapStore()
        self.source = ProceduralSource(self)
        self.slot_of: dict[str, int] = {}
        self.doc_id = [""] * self.slots
        for slot in range(self.slots):
            self._create(slot)
        self.sweep = -1
        self.cur_times = None
        self.cur_values = None
        self.base_times = None
        self.base_values = None
        self.spiked = (np.zeros(0, np.int64),) * 4
        self._next = None
        self._thread = None
        # the tap's books
        self.store.tap = self._tap
        self.writes: list[tuple] = []  # (perf_counter, windows, docs)
        self.terminal: list[int] = []
        self.captured: list[tuple] = []  # (slot, sweep, uid, fit_pos, status, reason, info)
        self.sent: dict[tuple, np.ndarray] = {}  # (slot, sweep) -> window [F, w]
        self.sent_base: dict[tuple, np.ndarray] = {}  # canary docs: baseline [F, points]
        self.capture = np.zeros(self.slots, bool)
        self.sample = np.zeros(0, np.int64)
        self.unexpected = 0

    # -- documents -------------------------------------------------------

    def _create(self, slot: int) -> None:
        g = self.groups[self.group_of[slot]]
        uid = int(self.uid[slot])
        canary = bool(g.get("baseline_window"))
        cur, hist, base = [], [], []
        for j, a in enumerate(g["aliases"]):
            cu = f"http://prom/cur?q={a}:app{uid}&step={self.step}"
            hu = f"http://prom/hist?q={a}:app{uid}&end={self.hist_end}&step={self.step}"
            self.source.index[cu] = (0, slot, j)
            self.source.index[hu] = (1, slot, j)
            cur.append(f"{a}== {cu}")
            hist.append(f"{a}== {hu}")
            if canary:
                bu = f"http://prom/base?q={a}:app{uid}&step={self.step}"
                self.source.index[bu] = (2, slot, j)
                base.append(f"{a}== {bu}")
        doc = Document(
            id=f"job-{uid}-{int(self.gen[slot])}",
            app_name=f"app{uid}",
            end_time=self.end_time,
            current_config=" ||".join(cur),
            historical_config=" ||".join(hist),
            baseline_config=" ||".join(base),
            strategy="canary" if canary else "continuous",
        )
        self.store.create(doc)
        self.slot_of[doc.id] = slot
        self.doc_id[slot] = doc.id

    def _retire(self, slot: int) -> None:
        did = self.doc_id[slot]
        with self.store._lock:
            self.store._docs.pop(did, None)
        self.slot_of.pop(did, None)

    def history(self, slot: int) -> np.ndarray:
        return series.history(
            self.fleet_seed, int(self.uid[slot]), int(self.nwin[slot]), self.n_hist, self.fam
        )

    # -- traffic ---------------------------------------------------------

    def now(self, sweep: int) -> float:
        return float(self.hist_end + self.step * (sweep + self.w) + 5)

    def _draw(self, sweep: int):
        values, spikes, bases = series.draw_sweep(
            self.seed, sweep, self.groups, (self.group_of, self.local), self.w,
            self.n_hist, self.fam, self.traffic,
        )
        times = self.hist_end + self.step * (sweep + np.arange(self.w, dtype=np.int64))
        return times, values, spikes, bases

    def prefetch(self, sweep: int) -> None:
        """Draw the next sweep's windows on a side thread while the
        current sweep runs (numpy's generators release the GIL)."""
        out = {}

        def work():
            out["v"] = self._draw(sweep)

        self._thread = threading.Thread(target=work, daemon=True)
        self._next = (sweep, out)
        self._thread.start()

    def begin_sweep(self, sweep: int) -> float:
        """Install sweep `sweep`'s windows; returns the tick's `now`."""
        if self._next is not None and self._next[0] == sweep:
            self._thread.join()
            drawn = self._next[1]["v"]
        else:
            drawn = self._draw(sweep)
        self._next = None
        self.cur_times, self.cur_values, self.spiked, self.base_values = drawn
        # a baseline is the window the same length of time before the
        # current one, a day earlier: the same phase of the daily cycle
        points = max([self.w] + [b.shape[-1] for b in self.base_values if b is not None])
        self.base_times = (
            int(self.cur_times[0]) - 86_400 + self.step * np.arange(points, dtype=np.int64)
        )
        self.sweep = sweep
        self.capture[:] = False
        self.capture[self.sample] = True
        self.capture[self.spiked[0]] = True
        for s in np.flatnonzero(self.capture):
            g, i = self.group_of[s], self.local[s]
            self.sent[(int(s), sweep)] = self.cur_values[g][i].copy()
            if self.base_values[g] is not None:
                self.sent_base[(int(s), sweep)] = self.base_values[g][i].copy()
        return self.now(sweep)

    def draw_sample(self, n: int) -> None:
        self.sample = series.sample(self.seed, self.slots, n)

    def end_sweep(self) -> int:
        """After a tick returns: follow every doc that went terminal with
        a new job of the same service over the same history range. The new
        job queues where the store puts it, at the end (the order a claim
        sorted by `modifiedAt` gives): a fleet's slices drift from the
        proportions they were created in. Returns the docs followed."""
        term, self.terminal = self.terminal, []
        for slot in term:
            self._retire(slot)
            self.gen[slot] += 1
            self._create(slot)
        return len(term)

    # -- the tap ---------------------------------------------------------

    def _tap(self, docs) -> None:
        t = time.perf_counter()
        wins = 0
        n = 0
        slot_of, nwin, capture, sweep = self.slot_of, self.nwin, self.capture, self.sweep
        for d in docs:
            slot = slot_of.get(d.id)
            if slot is None:
                self.unexpected += 1
                continue
            st = d.status
            if st != STATUS_PREPROCESS_COMPLETED:
                if st in TERMINAL_STATUSES:
                    self.terminal.append(slot)
                else:
                    self.unexpected += 1
                    continue
            wins += nwin[slot]
            n += 1
            if capture[slot]:
                self.captured.append(
                    (slot, sweep, int(self.uid[slot]), int(self.fit_pos[slot]),
                     st, d.reason, d.anomaly_info)
                )
        self.writes.append((t, int(wins), n, sweep))
