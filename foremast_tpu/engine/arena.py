"""Device-resident fitted-state arena: gather-keyed warm-tick scoring.

Round 3 cached stacked terminal state keyed by the ORDERED TUPLE of the
whole claim set's fit keys: any churn — one job finishing, one arriving,
claim-order jitter — missed the tuple key and silently re-paid a full
host restack + upload (~25 MB/tick at the daily season width). The
arena replaces that with one device-resident ROW per fit key:

  * state lives in HBM as [capacity] vectors + a [capacity, m] season
    buffer; a tick's batch is assembled ON DEVICE by `jnp.take` with a
    [B] row-index array inside the scoring program (engine.scoring.
    score_from_arena) — zero host restack for warm rows;
  * a churned claim set re-uploads exactly the changed rows (scatter of
    the fitted entries into their rows), so 10% churn costs 10%;
  * capacity is sized by BYTES, not entries (a row's footprint varies
    360x between m=1 and m=1440) — FOREMAST_ARENA_BYTES, default 256 MB
    — with a row-count ceiling so tiny rows cannot demand a multi-
    million-row index space;
  * hit/miss/eviction counters are exported through the worker's
    self-telemetry (observe.gauges).

The host-side fit cache (models.cache.ModelCache of terminal-state
tuples) stays authoritative — it is what checkpoints and what multihost
workers key — the arena is a device-side acceleration of it. Eviction
safety: every fit-cache miss is refit and force-scattered, so a stale
arena row can never outlive its host entry's eviction.

Reference anchor: this accelerates the brain's model cache semantics
(`foremast-brain/README.md:30` MAX_CACHE_SIZE) for the re-check loop
(`design.md:43`), where the reference refits from the full history.
"""

from __future__ import annotations

import logging
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from foremast_tpu.observe.spans import span

log = logging.getLogger("foremast_tpu.arena")

_DEFAULT_BYTES = 256 * 1024 * 1024
# Hard auto-grow ceiling: a fleet whose working set exceeds the soft
# budget grows the arena rather than silently restacking every tick
# (VERDICT r4: at m=1440 the 256 MB default held ~46k rows, so a daily
# fleet >= ~11.6k services fell off a per-tick re-upload cliff with no
# counter and no log). 2 GB holds the row ceiling even at m=1440
# (262,144 rows x 5,780 B = 1.45 GB) and is ~12% of a v5e chip's HBM.
_DEFAULT_MAX_BYTES = 2 * 1024 * 1024 * 1024
_MAX_ROWS = 262_144
_MIN_ROWS = 8_192


# Explicit overrides beat the env: pod-mode followers adopt the
# leader's broadcast budgets via set_arena_budget() — mutating
# os.environ after worker threads exist is a cross-thread race, and the
# write would only reach code that happens to re-read the env.
_BYTES_OVERRIDE: int | None = None
_MAX_BYTES_OVERRIDE: int | None = None


def set_arena_budget(
    soft_bytes: int | None, max_bytes: int | None
) -> None:
    """Pin the arena byte budgets for this process (None clears an
    override back to env/default). Call BEFORE the first tick: existing
    arenas keep the capacity they were built with."""
    global _BYTES_OVERRIDE, _MAX_BYTES_OVERRIDE
    _BYTES_OVERRIDE = None if soft_bytes is None else int(soft_bytes)
    _MAX_BYTES_OVERRIDE = None if max_bytes is None else int(max_bytes)


def _arena_bytes() -> int:
    if _BYTES_OVERRIDE is not None:
        return _BYTES_OVERRIDE
    return int(os.environ.get("FOREMAST_ARENA_BYTES", _DEFAULT_BYTES))


def _arena_max_bytes() -> int:
    if _MAX_BYTES_OVERRIDE is not None:
        return _MAX_BYTES_OVERRIDE
    return int(
        os.environ.get("FOREMAST_ARENA_MAX_BYTES", _DEFAULT_MAX_BYTES)
    )


def _row_bytes(m: int) -> int:
    # level f32 + trend f32 + phase i32 + scale f32 + n_hist i32 + season
    return 20 + 4 * m


def _is_pad_key(k) -> bool:
    """Batch-padding keys (judge/joint columnar "__pad__*" strings) —
    resident arena machinery that must never read as fleet state in the
    operator counters (models.cache.is_pad_fit_key is the fit-cache
    twin; arena keys that are pads are always plain strings)."""
    return isinstance(k, str) and k.startswith("__pad__")


def _pow2(n: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return b


class _ArenaTenancy:
    """Arena-rows budget envelopes + eviction attribution (ISSUE 20).

    Tracks which tenant owns each KEYED resident row (resolved once at
    assignment from the fit key's URL-encoded tenant label, cached by
    the registry) so `assign` can (a) recycle an over-envelope tenant's
    OWN least-recent row instead of evicting a neighbor's, and (b)
    charge every eviction to the tenant whose allocation forced it.
    Pads and transients stay untenanted. Single-threaded like the arena
    itself; the accounting ledger flush at the end of each assign call
    is the only lock it ever touches (tenant.accounting, a leaf)."""

    __slots__ = ("registry", "acct", "envelopes", "rows", "of", "_pending")

    def __init__(self, registry, acct):
        self.registry = registry
        self.acct = acct
        self.envelopes = {
            name: s.arena_rows
            for name, s in registry.specs.items()
            if s.arena_rows > 0
        }
        self.rows: dict = {}  # tenant -> keyed resident row count
        self.of: dict = {}  # fit key -> tenant
        self._pending: dict = {}  # tenant -> evictions this assign call

    @staticmethod
    def build(tenancy=None):
        """A tracker when the process is tenanted and tenancy could
        matter here (>=2 tenants, or any arena_rows envelope), else
        None — the parity pin: an untenanted or single-tenant arena
        keeps today's row placement byte-for-byte."""
        from foremast_tpu.tenant import accounting_for, get_tenancy

        if tenancy is None:
            tenancy = get_tenancy()
        if tenancy is None:
            return None
        if not (
            tenancy.fair
            or any(s.arena_rows > 0 for s in tenancy.specs.values())
        ):
            return None
        return _ArenaTenancy(tenancy, accounting_for(tenancy))

    def tenant_of(self, key):
        """Owning tenant for a REAL fit key (callers skip pads/None)."""
        return self.registry.tenant_of_key(key)

    def note_assign(self, key, tenant) -> None:
        self.of[key] = tenant
        self.rows[tenant] = self.rows.get(tenant, 0) + 1

    def note_drop(self, key) -> None:
        t = self.of.pop(key, None)
        if t is not None:
            left = self.rows.get(t, 0) - 1
            if left > 0:
                self.rows[t] = left
            else:
                self.rows.pop(t, None)

    def over(self, tenant) -> bool:
        env = self.envelopes.get(tenant, 0)
        return env > 0 and self.rows.get(tenant, 0) >= env

    def charge(self, tenant) -> None:
        self._pending[tenant] = self._pending.get(tenant, 0) + 1

    def flush(self) -> None:
        if self._pending:
            for t, n in self._pending.items():
                self.acct.count_eviction(t, n)
            self._pending.clear()

    def clear(self) -> None:
        self.rows.clear()
        self.of.clear()
        self._pending.clear()


@partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5))
def _scatter(level, trend, season, phase, scale, nh, idx, l_n, t_n, s_n, p_n, sc_n, n_n):
    """Functional in-place row update (donated buffers: the arena is the
    sole owner, so XLA reuses the allocation instead of copying)."""
    return (
        level.at[idx].set(l_n),
        trend.at[idx].set(t_n),
        season.at[idx].set(s_n),
        phase.at[idx].set(p_n),
        scale.at[idx].set(sc_n),
        nh.at[idx].set(n_n),
    )


@partial(jax.jit, donate_argnums=(0,))
def _scatter_tree(state, idx, updates):
    """Row scatter for an arbitrary state pytree (TreeArena): every leaf
    is [capacity, ...] and receives its [width, ...] update slab at the
    same row indices. Donated like `_scatter` — the arena owns the sole
    reference, so XLA updates in place."""
    return jax.tree.map(lambda s, u: s.at[idx].set(u), state, updates)


class RowArena:
    """Row-assignment machinery shared by every device state arena:
    byte-budgeted capacity with pow2 auto-grow toward the hard cap,
    approximate-LRU recycling, hit/miss/eviction counters, and the
    per-call transient-row aging. Subclasses own the actual device
    buffer layout via `_alloc` / `_grow` (and their own `scatter`).

    Not thread-safe by design: an arena belongs to a single judge's
    scoring thread (the worker is the only writer, and ModelCache
    remains the concurrent-visible layer)."""

    def __init__(
        self,
        row_bytes: int,
        max_bytes: int | None = None,
        sharding=None,
        shards: int = 1,
    ):
        """`sharding` (optional jax.sharding.Sharding) places the arena's
        device buffers explicitly — a ShardedJudge passes a mesh
        NamedSharding so the warm-tick gather runs locally on every
        device instead of pulling rows from wherever jnp.zeros happened
        to commit them (VERDICT r4 weak #4: the arena's placement under
        GSPMD was inherited by accident).

        `shards` > 1 (ISSUE 19) partitions the row space along the same
        data axis as the batch: the [capacity] leading axis splits into
        `shards` contiguous blocks of `cap_s` rows each (global row
        g = shard * cap_s + local), `sharding` must block-shard the
        leading axis over that axis, and `assign` places position i of a
        B-row batch ONLY in shard i // (B / shards) — the batch's own
        block placement — so the warm gather is device-local by
        construction. Byte budgets (`max_rows` / `hard_rows`) are
        PER-SHARD: each device hosts its block within the same budget,
        so aggregate capacity scales linearly with the mesh
        (`device_bytes()` stays per-device in both modes)."""
        self.row_bytes = max(int(row_bytes), 1)
        self.sharding = sharding
        self.shards = max(int(shards), 1)
        budget = _arena_bytes() if max_bytes is None else max_bytes
        self.max_rows = min(_MAX_ROWS, max(budget // self.row_bytes, 8))
        # soft budget: a batch larger than max_rows auto-grows toward the
        # hard cap (one log per growth) instead of silently thrashing or
        # falling back; only past hard_rows does assign() refuse
        self.hard_rows = min(
            _MAX_ROWS,
            max(_arena_max_bytes() // self.row_bytes, 8),
        )
        self.cap = 0  # TOTAL rows (= shards * cap_s when sharded)
        self.cap_s = 0  # per-shard rows (== cap when shards == 1)
        self.state = None  # layout owned by the subclass
        self.rows: dict = {}  # fit key -> row index
        self.row_key: list = []  # row index -> fit key | None
        # fit key -> the host entry OBJECT its row was scattered from:
        # joint-path refresh detection (an entry replaced under the same
        # key means the device row is stale) compares by identity, the
        # same contract as the worker's admission revalidation. Kept by
        # callers that scatter whole-entry rows (TreeArena users);
        # evictions prune it so it never outgrows the row count.
        self.row_entry: dict = {}
        self.free: list[int] = []  # unassigned row indices (shards == 1)
        # sharded mode: per-shard free lists of LOCAL indices (local
        # indices are stable across growth; global ones renumber)
        self._free_s: list[list[int]] = [[] for _ in range(self.shards)]
        self._transients: list[int] = []  # last call's unkeyed rows
        self.stamp = np.zeros(0, np.int64)  # per-row last-use tick
        self.tick = 0
        self.hits = 0
        self.misses = 0  # rows scattered (new or refreshed)
        self.evictions = 0
        self.shard_moves = 0  # rows migrated between shards (sharded)
        # resident rows held by batch-padding keys ("__pad__*"): arena
        # machinery, not fleet state — subtracted from rows_live so the
        # operator counters report documents, and their hits/misses are
        # never counted (positions >= assign()'s n_real are pads)
        self.pad_live = 0
        # called with the key of every row `assign` recycles: an owner
        # whose rows cannot be scattered again from a host entry (the
        # backbone's prefix cache) drops what pointed at the row
        self.on_evict = None
        # multi-tenant QoS (ISSUE 20): None unless the process is
        # tenanted with >=2 tenants or an arena_rows envelope — the
        # untenanted arena keeps today's placement byte-for-byte
        self._qos = _ArenaTenancy.build()

    # -- layout hooks (subclass-owned) ------------------------------------

    def _alloc(self, cap: int):
        """Fresh all-zero state for `cap` rows."""
        raise NotImplementedError

    def _grow(self, pad: int):
        """`self.state` extended by `pad` zero rows."""
        raise NotImplementedError

    # -- memory ----------------------------------------------------------

    def _ensure_capacity(self, need: int) -> bool:
        """Grow (doubling) to host `need` concurrent rows; False when even
        the hard byte cap cannot fit the batch (caller falls back to a
        one-off stacked dispatch — counted, never silent)."""
        if need > self.max_rows:
            if need > self.hard_rows:
                return False
            # auto-grow past the soft budget: an LRU arena smaller than
            # the fleet's working set thrashes (cyclic access misses every
            # row, re-uploading the whole fleet's state each tick), so the
            # budget is treated as a default, not a wall. Grow to the
            # next power of two (capped at the hard limit) so a fleet
            # that adds a few services per tick amortizes growth instead
            # of reallocating + retracing at every new exact size.
            self.max_rows = min(self.hard_rows, _pow2(need))
            log.warning(
                "arena grown past FOREMAST_ARENA_BYTES soft budget: "
                "%d rows x %d B = %.0f MB; set FOREMAST_ARENA_BYTES>=%d "
                "to silence",
                need,
                self.row_bytes,
                need * self.row_bytes / 1e6,
                need * self.row_bytes,
            )
        if need <= self.cap:
            return True
        new_cap = min(self.max_rows, max(_pow2(need), self._min_rows()))
        pad = new_cap - self.cap
        if self.state is None:
            self.state = self._alloc(new_cap)
        else:
            self.state = self._grow(pad)
        if self.sharding is not None:
            # explicit placement (replicated over the judge's mesh); a
            # handful of device_puts per growth, never per tick
            self.state = jax.device_put(self.state, self.sharding)
        self.row_key.extend([None] * pad)
        self.stamp = np.concatenate(
            [self.stamp, np.full(pad, -1, np.int64)]
        )
        self.free.extend(range(self.cap, new_cap))
        self.cap = new_cap
        self.cap_s = new_cap
        return True

    # Growth IS a sanctioned host round-trip (rare — pow2 doubling,
    # warn-logged): _grow_sharded device_gets every leaf so the row
    # blocks survive the resize (see its docstring), and the index
    # renumbering below is host metadata work on that boundary.
    # foremast: device-boundary
    def _ensure_capacity_sharded(self, need_s: int) -> bool:
        """Sharded-mode `_ensure_capacity`: `need_s` is the PER-SHARD row
        count this call must host. Same soft-budget auto-grow / hard-cap
        refusal rules as the replicated path, applied per shard."""
        if need_s > self.max_rows:
            if need_s > self.hard_rows:
                return False
            self.max_rows = min(self.hard_rows, _pow2(need_s))
            log.warning(
                "sharded arena grown past FOREMAST_ARENA_BYTES soft "
                "budget: %d rows/shard x %d shards x %d B = %.0f MB "
                "aggregate; set FOREMAST_ARENA_BYTES>=%d to silence",
                need_s,
                self.shards,
                self.row_bytes,
                need_s * self.shards * self.row_bytes / 1e6,
                need_s * self.row_bytes,
            )
        if need_s <= self.cap_s:
            return True
        new_s = min(
            self.max_rows,
            max(_pow2(need_s), max(self._min_rows() // self.shards, 8)),
        )
        old_s = self.cap_s
        if self.state is None:
            self.state = self._alloc(self.shards * new_s)
        else:
            self.state = self._grow_sharded(old_s, new_s)
        if self.sharding is not None:
            self.state = jax.device_put(self.state, self.sharding)
        # host-side renumbering: global row g = shard * cap_s + local, so
        # growing cap_s moves every existing global index
        if old_s:
            def remap(g: int) -> int:
                return (g // old_s) * new_s + (g % old_s)

            self.rows = {k: remap(g) for k, g in self.rows.items()}
            new_keys: list = [None] * (self.shards * new_s)
            for g, k in enumerate(self.row_key):
                if k is not None:
                    new_keys[remap(g)] = k
            self.row_key = new_keys
            st = np.full((self.shards, new_s), -1, np.int64)
            st[:, :old_s] = self.stamp.reshape(self.shards, old_s)
            self.stamp = st.ravel()
            self._transients = [remap(g) for g in self._transients]
        else:
            self.row_key = [None] * (self.shards * new_s)
            self.stamp = np.full(self.shards * new_s, -1, np.int64)
        for s in range(self.shards):
            self._free_s[s].extend(range(old_s, new_s))
        self.cap_s = new_s
        self.cap = self.shards * new_s
        return True

    def _grow_sharded(self, old_s: int, new_s: int):
        """Per-shard zero-padding of every state leaf. Growth is RARE
        (pow2 doubling, warn-logged), so it round-trips through the
        host: a plain `jnp.concatenate` on a data-axis-sharded leaf
        would RE-BLOCK the layout under GSPMD — existing rows silently
        migrate devices and their global indices stop matching the block
        rule — while the host reshape keeps every row in its shard."""
        shards = self.shards

        def pad_leaf(x):
            h = np.asarray(jax.device_get(x))
            h = h.reshape(shards, old_s, *h.shape[1:])
            widths = [(0, 0), (0, new_s - old_s)] + [(0, 0)] * (h.ndim - 2)
            h = np.pad(h, widths)
            return h.reshape(shards * new_s, *h.shape[2:])

        return jax.tree.map(pad_leaf, self.state)

    def _min_rows(self) -> int:
        """Initial-allocation floor (subclasses with fat rows lower it:
        pre-allocating 8,192 LSTM rows would burn ~0.5 GB on a 10-job
        fleet)."""
        return _MIN_ROWS

    def clear(self) -> None:
        """Release device buffers and all row assignments."""
        self.cap = 0
        self.cap_s = 0
        self.state = None
        self.rows.clear()
        self.row_entry.clear()
        self.row_key = []
        self.stamp = np.zeros(0, np.int64)
        self.free = []
        self._free_s = [[] for _ in range(self.shards)]
        self._transients = []
        self.pad_live = 0
        if self._qos is not None:
            self._qos.clear()

    # -- assignment ------------------------------------------------------

    def _own_victim(self, order, tenant, base: int = 0) -> int:
        """First evictable row (stamp != this call's tick) OWNED by
        `tenant`, walking `order` (a stamp argsort — LRU first; local
        indices offset by `base` in sharded mode). -1 when every row of
        the tenant is protected this call — the envelope then falls
        through to normal placement, because a budget may reorder row
        recycling but must never block a verdict (ISSUE 20 parity)."""
        qos = self._qos
        for lr in order.tolist():
            r = base + lr
            if self.stamp[r] == self.tick:
                continue
            k = self.row_key[r]
            if k is not None and qos.of.get(k) == tenant:
                return r
        return -1

    def assign(
        self, keys, force, n_real: int | None = None
    ) -> tuple[np.ndarray, list[int]] | None:
        """Map a batch's fit keys onto arena rows.

        keys:  per-task cache keys (None => transient row, scattered and
               immediately recyclable).
        force: positions whose entries were (re)fitted this tick — their
               rows must be scattered even if the key already has a row
               (a fit-cache miss means the host entry was refreshed; the
               old device row is stale).
        n_real: positions >= this are batch-padding keys ("__pad__*"):
               they get rows and scatters like any key (stable pad rows
               keep warm ticks scatter-free) but are excluded from the
               hit/miss/rows_live counters — operators count documents,
               not padding. Default: every position is real.

        Returns (rows [B] int64, scatter_positions) or None when the
        batch cannot fit in the byte budget.

        The warm-tick hit pass is a single C-level dict sweep
        (np.fromiter) plus one fancy-index stamp update — on a fleet
        tick this runs for 40k+ keys with zero scatters, so per-key
        interpreter work is what would dominate. Rows touched this call
        carry stamp == tick and are never eviction candidates; last
        call's transient rows are aged to stamp -1 up front, making them
        the preferred recycling pool.

        Sharded arenas (`shards` > 1) route to `_assign_sharded`: the
        same surface, with rows constrained to each position's data-axis
        block.
        """
        if self.shards > 1:
            return self._assign_sharded(keys, force, n_real)
        # age out the previous call's transient rows (unless a keyed
        # assignment has since claimed the row)
        for r in self._transients:
            if self.row_key[r] is None:
                self.stamp[r] = -1
        self._transients.clear()
        self.tick += 1
        n = len(keys)
        nr = n if n_real is None else n_real
        if not self._ensure_capacity(n):
            return None
        getrow = self.rows.get
        rows = np.fromiter(
            ((getrow(k, -1) if k is not None else -1) for k in keys),
            np.int64,
            count=n,
        )
        hit = rows >= 0
        if hit.any():
            self.stamp[rows[hit]] = self.tick
        nhits = int(hit[:nr].sum())
        scatter: list[int] = []
        if force:
            for i in force:
                if hit[i]:
                    scatter.append(i)
            nhits -= len(scatter)
            self.misses += len(scatter)
        self.hits += nhits
        alloc = np.nonzero(~hit)[0]
        if len(alloc):
            # Working-set growth (ISSUE 14): a warm tick SPLIT across
            # sibling bucket calls (the baseline-less and canary
            # columnar buckets share this arena) has a working set
            # larger than any single batch, but capacity only ever grew
            # to the largest batch — so each bucket would evict the
            # rows its sibling used ONE call ago and the whole fleet
            # state would re-scatter every tick (LRU thrash, the exact
            # failure mode the auto-grow comment in _ensure_capacity
            # describes). Rows touched within the last two calls are
            # treated as resident working set: when the allocation
            # cannot be served from free + genuinely stale rows, grow
            # (same soft-budget warning / hard-cap rules) instead of
            # recycling them.
            # available = the free pool plus assigned rows idle for 3+
            # calls (free rows keep stamp -1 and never re-enter `free`
            # after assignment, so the two sets are disjoint; aged
            # transients undercount here, which at worst grows a little
            # early — never thrashes)
            available = len(self.free) + int(
                ((self.stamp >= 0) & (self.stamp < self.tick - 2)).sum()
            )
            shortfall = len(alloc) - available
            if shortfall > 0 and self.cap + shortfall <= self.hard_rows:
                self._ensure_capacity(self.cap + shortfall)
            qos = self._qos
            order = None
            oi = 0
            for ai, i in enumerate(alloc.tolist()):
                alloc_left = len(alloc) - ai  # incl. this allocation
                k = keys[i]
                if k is not None:
                    r = getrow(k, -1)
                    if r >= 0:
                        # duplicate key later in the same batch: reuse
                        # the row its first occurrence just claimed
                        rows[i] = r
                        continue
                tenant = None
                if qos is not None and k is not None and not _is_pad_key(k):
                    tenant = qos.tenant_of(k)
                if tenant is not None and qos.over(tenant):
                    # arena_rows envelope: an over-budget tenant
                    # recycles its OWN least-recent row — never a
                    # neighbor's, never the free pool, never capacity
                    # growth — and the eviction is charged to it
                    if order is None:
                        order = np.argsort(self.stamp, kind="stable")
                    rv = self._own_victim(order, tenant)
                    if rv >= 0:
                        old = self.row_key[rv]
                        del self.rows[old]
                        self.row_entry.pop(old, None)
                        self.evictions += 1
                        if self.on_evict is not None:
                            self.on_evict(old)
                        qos.note_drop(old)
                        qos.charge(tenant)
                        self.rows[k] = rv
                        self.row_key[rv] = k
                        qos.note_assign(k, tenant)
                        self.stamp[rv] = self.tick
                        rows[i] = rv
                        scatter.append(i)
                        if i < nr:
                            self.misses += 1
                        continue
                if not self.free:
                    if order is None:
                        order = np.argsort(self.stamp, kind="stable")
                    # In-loop anti-thrash backstop (the pre-loop
                    # estimate's 2-call recency window under-protects
                    # when 3+ assigns share the arena per tick cycle:
                    # uni + canary + several slow-path buckets). Peek
                    # the next eviction candidate without consuming it;
                    # if it was used within the last 8 calls the
                    # working set genuinely exceeds capacity — grow
                    # ONCE for the remaining allocations (same
                    # soft-budget warning / hard-cap rules) instead of
                    # recycling live rows every tick. A row idle for
                    # 8+ assign calls is cold under any real tick shape.
                    pi = oi
                    while pi < len(order) and self.stamp[order[pi]] == self.tick:
                        pi += 1
                    if (
                        pi < len(order)
                        and self.stamp[order[pi]] >= self.tick - 8
                        and self.cap + alloc_left <= self.hard_rows
                    ):
                        self._ensure_capacity(self.cap + alloc_left)
                if self.free:
                    r = self.free.pop()
                else:
                    while True:
                        if oi >= len(order):
                            # Unreachable by construction: _ensure_capacity
                            # guaranteed cap >= n, and at most n rows can
                            # carry this call's stamp, so an evictable row
                            # always exists. Returning None here would
                            # leave rows/row_key/stamp partially mutated
                            # with device state never scattered — a later
                            # tick would gather garbage as a warm hit
                            # (ADVICE r4) — so fail loudly instead.
                            raise RuntimeError(
                                "StateArena.assign invariant violated: "
                                f"no evictable row (need={n}, cap={self.cap})"
                            )
                        r = int(order[oi])
                        oi += 1
                        # current stamp, not the argsort snapshot: rows
                        # touched THIS call (hits and fresh allocs) are
                        # protected
                        if self.stamp[r] != self.tick:
                            break
                    old = self.row_key[r]
                    if old is not None:
                        del self.rows[old]
                        self.row_entry.pop(old, None)
                        self.evictions += 1
                        if self.on_evict is not None:
                            self.on_evict(old)
                        if _is_pad_key(old):
                            self.pad_live -= 1
                        if qos is not None:
                            qos.note_drop(old)
                            if tenant is not None:
                                qos.charge(tenant)
                if k is not None:
                    self.rows[k] = r
                    self.row_key[r] = k
                    if i >= nr:
                        self.pad_live += 1
                    if tenant is not None:
                        qos.note_assign(k, tenant)
                else:
                    # transient: recyclable at the next assign
                    self.row_key[r] = None
                    self._transients.append(r)
                self.stamp[r] = self.tick
                rows[i] = r
                scatter.append(i)
                if i < nr:
                    self.misses += 1
        if self._qos is not None:
            self._qos.flush()
        return rows, scatter

    def _assign_sharded(
        self, keys, force, n_real: int | None = None
    ) -> tuple[np.ndarray, list[int]] | None:
        """`assign` under the data-axis block placement rule (ISSUE 19):
        position i of a B-row batch lives in shard i // (B / shards) and
        its row must belong to that shard's block (global row
        g = shard * cap_s + local), so the warm gather never crosses a
        device boundary. Differences from the replicated path, all
        bounded and counted:

          * a key whose position moved to a different block since last
            tick MIGRATES — old row freed, fresh row scattered in the
            new shard (`shard_moves` counts these; claim-order jitter is
            self-healing, one re-scatter per moved row);
          * a key already claimed by one position this call but ALSO
            appearing at a position of another shard (duplicate keys —
            shard-qualified pad keys never collide) scores that position
            from a transient row;
          * ALL growth happens before rows are handed out (growing
            renumbers global indices, which would corrupt positions
            already assigned this call), using the same 8-call idle
            window the replicated path's in-loop backstop uses — at
            worst it grows a little earlier, never thrashes.
        """
        n = len(keys)
        nr = n if n_real is None else n_real
        shards = self.shards
        if n % shards:
            log.warning(
                "sharded arena assign: batch of %d rows is not a "
                "multiple of %d shards — stacked fallback", n, shards,
            )
            return None
        for r in self._transients:
            if self.row_key[r] is None:
                self.stamp[r] = -1
        self._transients.clear()
        self.tick += 1
        per = n // shards
        if not self._ensure_capacity_sharded(per):
            return None
        getrow = self.rows.get

        def sweep() -> np.ndarray:
            return np.fromiter(
                ((getrow(k, -1) if k is not None else -1) for k in keys),
                np.int64,
                count=n,
            )

        rows = sweep()
        shard_of = np.repeat(np.arange(shards, dtype=np.int64), per)
        hit = (rows >= 0) & ((rows // self.cap_s) == shard_of)
        miss_shard = shard_of[~hit]
        if len(miss_shard):
            counts = np.bincount(miss_shard, minlength=shards)
            st2 = self.stamp.reshape(shards, self.cap_s)
            idle = ((st2 >= 0) & (st2 < self.tick - 8)).sum(axis=1)
            free_n = np.asarray([len(f) for f in self._free_s])
            short = int((counts - idle - free_n).max())
            if short > 0 and self.cap_s + short <= self.hard_rows:
                self._ensure_capacity_sharded(self.cap_s + short)
                rows = sweep()  # growth renumbered every global index
                hit = (rows >= 0) & ((rows // self.cap_s) == shard_of)
        if hit.any():
            self.stamp[rows[hit]] = self.tick
        nhits = int(hit[:nr].sum())
        scatter: list[int] = []
        if force:
            for i in force:
                if hit[i]:
                    scatter.append(i)
            nhits -= len(scatter)
            self.misses += len(scatter)
        self.hits += nhits
        alloc = np.nonzero(~hit)[0]
        if len(alloc):
            claimed = {
                keys[i] for i in np.nonzero(hit)[0] if keys[i] is not None
            }
            qos = self._qos
            cap_s = self.cap_s
            order_s: list = [None] * shards
            oi_s = [0] * shards
            for i in alloc.tolist():
                k = keys[i]
                s = int(shard_of[i])
                base = s * cap_s
                transient = k is None
                if k is not None:
                    g = getrow(k, -1)
                    if g >= 0:
                        if g // cap_s == s:
                            # duplicate key later in the batch: reuse the
                            # row its first occurrence just claimed
                            rows[i] = g
                            continue
                        if k in claimed:
                            # the key's row legitimately belongs to
                            # another position this call — score this
                            # position from a transient copy
                            transient = True
                        else:
                            # block membership changed since last tick:
                            # migrate the row to this position's shard
                            self.row_key[g] = None
                            self.stamp[g] = -1
                            self._free_s[g // cap_s].append(g % cap_s)
                            del self.rows[k]
                            self.row_entry.pop(k, None)
                            self.shard_moves += 1
                            if _is_pad_key(k):
                                self.pad_live -= 1
                            if qos is not None:
                                # migration, not pressure: residency
                                # moves shards, nobody is charged (the
                                # note_assign below re-registers it)
                                qos.note_drop(k)
                tenant = None
                if qos is not None and not transient and not _is_pad_key(k):
                    tenant = qos.tenant_of(k)
                if tenant is not None and qos.over(tenant):
                    # arena_rows envelope, block-local: recycle the
                    # over-budget tenant's own least-recent row in THIS
                    # position's shard (placement stays device-local),
                    # charged to it; no candidate in the block → fall
                    # through to normal placement
                    if order_s[s] is None:
                        order_s[s] = np.argsort(
                            self.stamp[base : base + cap_s], kind="stable"
                        )
                    rv = self._own_victim(order_s[s], tenant, base)
                    if rv >= 0:
                        old = self.row_key[rv]
                        del self.rows[old]
                        self.row_entry.pop(old, None)
                        self.evictions += 1
                        qos.note_drop(old)
                        qos.charge(tenant)
                        self.rows[k] = rv
                        self.row_key[rv] = k
                        claimed.add(k)
                        qos.note_assign(k, tenant)
                        self.stamp[rv] = self.tick
                        rows[i] = rv
                        scatter.append(i)
                        if i < nr:
                            self.misses += 1
                        continue
                freel = self._free_s[s]
                if freel:
                    r = base + freel.pop()
                else:
                    if order_s[s] is None:
                        order_s[s] = np.argsort(
                            self.stamp[base : base + cap_s], kind="stable"
                        )
                    order = order_s[s]
                    oi = oi_s[s]
                    while True:
                        if oi >= len(order):
                            # mirror of the replicated invariant guard:
                            # cap_s >= per and at most `per` rows of a
                            # shard carry this call's stamp, so an
                            # evictable row always exists — fail loudly
                            # rather than gather garbage later
                            raise RuntimeError(
                                "sharded arena assign invariant "
                                f"violated: no evictable row in shard "
                                f"{s} (per={per}, cap_s={cap_s})"
                            )
                        r = base + int(order[oi])
                        oi += 1
                        if self.stamp[r] != self.tick:
                            break
                    oi_s[s] = oi
                    old = self.row_key[r]
                    if old is not None:
                        del self.rows[old]
                        self.row_entry.pop(old, None)
                        self.evictions += 1
                        if _is_pad_key(old):
                            self.pad_live -= 1
                        if qos is not None:
                            qos.note_drop(old)
                            if tenant is not None:
                                qos.charge(tenant)
                if transient:
                    self.row_key[r] = None
                    self._transients.append(r)
                else:
                    self.rows[k] = r
                    self.row_key[r] = k
                    claimed.add(k)
                    if i >= nr:
                        self.pad_live += 1
                    if tenant is not None:
                        qos.note_assign(k, tenant)
                self.stamp[r] = self.tick
                rows[i] = r
                scatter.append(i)
                if i < nr:
                    self.misses += 1
        if self._qos is not None:
            self._qos.flush()
        return rows, scatter

    def release(self, keys) -> None:
        """Give back the rows of `keys` (unsharded arenas): a row that was
        assigned for one judgment alone."""
        for k in keys:
            r = self.rows.pop(k, None)
            if r is None:
                continue
            self.row_entry.pop(k, None)
            self.row_key[r] = None
            self.stamp[r] = -1
            self.free.append(r)

    def device_bytes(self) -> int:
        """HBM footprint of this arena's buffers on ONE device: the full
        capacity when replicated (total cost = this x device count — the
        worker's device_mesh varz does that multiplication), one shard's
        block when data-axis sharded (so the same multiplication yields
        the SHARD-SUM — ISSUE 19 HBM accounting: adding chips adds
        capacity, not copies)."""
        return (self.cap // self.shards) * self.row_bytes

    def counters(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "rows_live": len(self.rows) - self.pad_live,
            "capacity_rows": self.cap,
            "shard_moves": self.shard_moves,
        }


class StateArena(RowArena):
    """Univariate fitted-forecast rows: [capacity] state vectors plus a
    [capacity, m] season buffer (the layout `scoring.score_from_arena`
    gathers)."""

    def __init__(
        self,
        season_len: int,
        max_bytes: int | None = None,
        sharding=None,
        shards: int = 1,
    ):
        self.m = max(int(season_len), 1)
        super().__init__(
            _row_bytes(self.m),
            max_bytes=max_bytes,
            sharding=sharding,
            shards=shards,
        )

    def _alloc(self, cap: int):
        return (
            jnp.zeros(cap, jnp.float32),
            jnp.zeros(cap, jnp.float32),
            jnp.zeros((cap, self.m), jnp.float32),
            jnp.zeros(cap, jnp.int32),
            jnp.zeros(cap, jnp.float32),
            jnp.zeros(cap, jnp.int32),
        )

    def _grow(self, pad: int):
        lvl, tr, se, ph, sc, nh = self.state
        zf = jnp.zeros(pad, jnp.float32)
        zi = jnp.zeros(pad, jnp.int32)
        return (
            jnp.concatenate([lvl, zf]),
            jnp.concatenate([tr, zf]),
            jnp.concatenate([se, jnp.zeros((pad, self.m), jnp.float32)]),
            jnp.concatenate([ph, zi]),
            jnp.concatenate([sc, zf]),
            jnp.concatenate([nh, zi]),
        )

    # -- data movement ---------------------------------------------------

    def scatter(self, rows: np.ndarray, positions: list[int], entries) -> None:
        """Upload the (re)fitted entries into their rows.

        entries[i] layout: (level, trend, season[np], phase, scale,
        n_hist) — the ModelCache terminal-state tuple. The scatter batch
        is padded to a power of two with duplicates of the first update
        (identical index+value duplicates are deterministic), bounding
        compiled shapes.
        """
        from foremast_tpu.engine import scoring

        k = len(positions)
        if k == 0:
            return
        # child of the judge's arena_assemble stage span: on the trace
        # timeline the scatter upload separates from the assign sweep
        # (churn cost shows as scatter width, not as opaque assemble time)
        with span("arena.scatter", rows=k, season_len=self.m, device=True):
            width = _pow2(k)
            idx = np.empty(width, np.int32)
            lvl = np.empty(width, np.float32)
            tr = np.empty(width, np.float32)
            se = np.empty((width, self.m), np.float32)
            ph = np.empty(width, np.int32)
            sc = np.empty(width, np.float32)
            nh = np.empty(width, np.int32)
            for j, i in enumerate(positions):
                e = entries[i]
                idx[j] = rows[i]
                lvl[j] = e[0]
                tr[j] = e[1]
                se[j] = scoring.tile_season(e[2], self.m)
                ph[j] = e[3]
                sc[j] = e[4]
                nh[j] = e[5]
            if k < width:
                idx[k:] = idx[0]
                lvl[k:] = lvl[0]
                tr[k:] = tr[0]
                se[k:] = se[0]
                ph[k:] = ph[0]
                sc[k:] = sc[0]
                nh[k:] = nh[0]
            self.state = _scatter(*self.state, idx, lvl, tr, se, ph, sc, nh)
            if self.shards > 1:
                # re-pin the block layout: GSPMD is free to solve the
                # global-index scatter by resharding, and the warm
                # gather's shard_map REQUIRES the data-axis blocks.
                # device_put is the identity when the layout survived;
                # scatter is the rare (miss/churn) path either way.
                self.state = jax.device_put(self.state, self.sharding)

    def counters(self) -> dict:
        out = super().counters()
        out["season_len"] = self.m
        return out


class TreeArena(RowArena):
    """Device-resident rows of an arbitrary fixed-shape state PYTREE —
    the joint-detector counterpart of `StateArena` (ISSUE 4 tentpole).

    One row holds everything a joint model needs to score warm: for the
    bivariate detector the fitted Gaussian (mean [2], cov [2, 2], valid);
    for the LSTM-AE hybrid the stacked `AEParams` leaves, the training
    error moments, and the residual-MVN state (per-metric HW terminal
    state, residual mean, covariance). The template fixes every leaf's
    per-row shape/dtype; capacity is the leading axis of every leaf, and
    warm batches are assembled ON DEVICE by `jnp.take` over a [B] row
    index inside the joint scoring programs
    (`multivariate.lstm_joint_score_from_rows`,
    `models.bivariate.detect_bivariate_from_rows`). Byte budgeting,
    pow2 auto-grow, LRU recycling and counters are inherited unchanged
    from `RowArena`."""

    def __init__(
        self,
        template,
        max_bytes: int | None = None,
        sharding=None,
        shards: int = 1,
        fixed_rows: int | None = None,
    ):
        """`template`: pytree of `jax.ShapeDtypeStruct` (or anything with
        .shape/.dtype) describing ONE row, without the capacity axis.

        `fixed_rows` fixes the capacity: the whole arena is allocated at
        the first assignment, the byte budgets are not consulted, it never
        grows (growth is a host round trip of every row, and a row of a
        prefix cache is tens of MB), and a batch larger than it is refused
        (`assign` returns None)."""
        self.template = template
        self.fixed_rows = None if fixed_rows is None else int(fixed_rows)
        leaves = jax.tree.leaves(template)
        # the template's leaves are ShapeDtypeStructs: shapes and dtypes,
        # no device value
        row_bytes = sum(  # foremast: ignore[device-flow]
            int(np.prod(leaf.shape, dtype=np.int64))
            * np.dtype(leaf.dtype).itemsize
            for leaf in leaves
        ) or 1
        super().__init__(
            row_bytes,
            max_bytes=max_bytes,
            sharding=sharding,
            shards=shards,
        )
        if self.fixed_rows is not None:
            self.max_rows = self.hard_rows = self.fixed_rows

    def _min_rows(self) -> int:
        # joint rows are fat (an f=4 LSTM-AE row is ~60 KB vs the
        # univariate daily row's ~5.8 KB); pre-allocating StateArena's
        # 8,192-row floor would burn ~0.5 GB of HBM on a 10-job fleet
        return 64 if self.fixed_rows is None else self.fixed_rows

    def _alloc(self, cap: int):
        return jax.tree.map(
            lambda leaf: jnp.zeros((cap, *leaf.shape), leaf.dtype),
            self.template,
        )

    def _grow(self, pad: int):
        if self.fixed_rows is not None:
            raise RuntimeError(
                f"TreeArena of fixed capacity {self.fixed_rows} asked to grow"
            )
        return jax.tree.map(
            lambda s, leaf: jnp.concatenate(
                [s, jnp.zeros((pad, *leaf.shape), leaf.dtype)]
            ),
            self.state,
            self.template,
        )

    # -- data movement ---------------------------------------------------

    def scatter(self, rows: np.ndarray, positions: list[int], entries) -> None:
        """Upload (re)fitted row pytrees into their rows.

        entries[i]: a pytree of HOST numpy leaves structurally matching
        the template (each leaf exactly the template's per-row shape —
        callers tile/pad season buffers beforehand). Same pow2
        width-padding discipline as `StateArena.scatter`."""
        k = len(positions)
        if k == 0:
            return
        with span("arena.scatter", rows=k, device=True):
            width = _pow2(k)
            idx = np.empty(width, np.int32)
            idx[:k] = [rows[i] for i in positions]
            idx[k:] = idx[0]
            picked = [entries[i] for i in positions]
            if k < width:
                picked.extend([picked[0]] * (width - k))
            updates = jax.tree.map(
                lambda *leaves: np.stack(leaves), *picked
            )
            self.state = _scatter_tree(self.state, idx, updates)
            if self.shards > 1:
                # same block-layout re-pin as StateArena.scatter
                self.state = jax.device_put(self.state, self.sharding)
