"""The seeded series family every cell's inputs are drawn from.

Co-moving seasonal metrics (the family `benchmarks/quality.py:draw_comoving`
uses, copied here and made procedural): a shared latent (daily sine +
noise) plus a per-metric offset and idiosyncratic noise. Nothing is held
in host memory: a service's history is a function of (seed, service uid)
and is drawn again whenever it is fetched, by the source or by the
reference's inputs alike.

Streams keep the draws apart: 0 histories, 1 current windows of a sweep,
2 spikes of a sweep, 3 the comparison's sample, 4 a canary group's baseline
windows and which of its docs really differ from them.
"""

from __future__ import annotations

import numpy as np

STREAM_HISTORY = 0
STREAM_WINDOWS = 1
STREAM_SPIKES = 2
STREAM_SAMPLE = 3
STREAM_BASELINE = 4

_U64 = (1 << 64) - 1


def generator(seed: int, stream: int, a: int = 0, b: int = 0) -> np.random.Generator:
    """A counter-based generator keyed by (seed, stream, a), counter b.
    Any whole seed works (the driver's are past 2**31)."""
    key = [(int(seed) * 8 + int(stream)) & _U64, int(a) & _U64]
    return np.random.Generator(np.random.Philox(key=key, counter=int(b)))


def _shape(z: np.ndarray, t: np.ndarray, fam: dict) -> np.ndarray:
    """z [..., F+1, n] standard normals -> [..., F, n] metric values at
    the absolute step indices t [n]."""
    f = z.shape[-2] - 1
    latent = (
        fam["latent_amp"] * np.sin(2.0 * np.pi * t / fam["period"])
        + fam["latent_sd"] * z[..., 0, :]
    )
    offs = 1.0 + fam["offset_step"] * np.arange(f, dtype=np.float32)
    out = offs[:, None] + latent[..., None, :] + fam["idio_sd"] * z[..., 1:, :]
    return out.astype(np.float32)


def history(seed: int, uid: int, f: int, n: int, fam: dict) -> np.ndarray:
    """One service's aligned history [F, n] f32, steps 0..n-1."""
    z = generator(seed, STREAM_HISTORY, uid).standard_normal(
        (f + 1, n), dtype=np.float32
    )
    return _shape(z, np.arange(n, dtype=np.float32), fam)


def _group_key(sweep: int, gi: int) -> int:
    """The key of a group's draws in one sweep: the fleet's first group
    keeps the sweep alone (what a one-group fleet always drew), every
    later group a key of its own, so that two groups of one metric count
    do not send each other's windows."""
    return int(sweep) + (int(gi) << 40)


def sweep_windows(
    seed: int, sweep: int, slots: int, f: int, w: int, t0: int, fam: dict,
    gi: int = 0, stream: int = STREAM_WINDOWS,
) -> np.ndarray:
    """Every slot's current window of one sweep, [slots, F, w] f32, at
    steps t0..t0+w-1 (the phase continues the history's). `stream`
    STREAM_BASELINE draws a canary group's baseline windows: the same
    family at the same phase, from a stream of their own."""
    z = generator(seed, stream, _group_key(sweep, gi), f).standard_normal(
        (slots, f + 1, w), dtype=np.float32
    )
    return _shape(z, (t0 + np.arange(w)).astype(np.float32), fam)


def shifted_docs(seed: int, sweep: int, gi: int, slots: int, share: float) -> np.ndarray:
    """[slots] bool: the docs of a canary group whose current window really
    differs from its baseline in this sweep (a seeded share of them)."""
    rng = generator(seed, STREAM_BASELINE, _group_key(sweep, gi), 1 << 32)
    return rng.random(slots) < float(share)


def sweep_spikes(seed: int, sweep: int, slots: int, f_of, w: int, tr: dict):
    """The slots spiked in one sweep: (slot [k], positions [k, points],
    signed magnitude [k], metric [k]). `tr` is the traffic mix. Kind
    "all" moves every metric of the service (metric -1); kind "break"
    moves ONE metric, either sign (a correlation break). Magnitudes are
    drawn between spike_lo and spike_hi (equal: one size). `f_of(slot)`
    gives a slot's metric count."""
    share = float(tr.get("spike_doc_share", 0.0))
    points = int(tr.get("spike_points", 1))
    k = max(int(round(slots * share)), 1) if share > 0 else 0
    rng = generator(seed, STREAM_SPIKES, sweep)
    slot = rng.choice(slots, size=min(k, slots), replace=False).astype(np.int64)
    n = len(slot)
    pos = np.zeros((n, points), np.int64)
    for i in range(n):
        pos[i] = rng.choice(w, size=points, replace=False)
    mag = rng.uniform(float(tr.get("spike_lo", 0.0)), float(tr.get("spike_hi", 0.0)), size=n)
    metric = np.full(n, -1, np.int64)
    if tr.get("spike_kind", "all") == "break":
        mag = mag * rng.choice([-1.0, 1.0], size=n)
        metric = np.array([rng.integers(0, f_of(int(s))) for s in slot], np.int64)
    return slot, pos, mag.astype(np.float32), metric


def apply_spike(window: np.ndarray, pos, mag, metric) -> None:
    """Add one service's spike to its window [F, w] in place."""
    if metric < 0:
        window[:, pos] += mag
    else:
        window[metric, pos] += mag


def slot_layout(groups: list) -> tuple[np.ndarray, np.ndarray]:
    """The fleet as an array of slots: (group of each slot, its index
    within the group). Creation order interleaves the groups in
    proportion, as a store filled by many teams' deployments would be."""
    counts = [int(g["services"]) for g in groups]
    order = np.concatenate(
        [(np.arange(c) + 0.5) / c + 1e-9 * gi for gi, c in enumerate(counts)]
    )
    gids = np.concatenate([np.full(c, gi) for gi, c in enumerate(counts)])
    group_of = gids[np.argsort(order, kind="stable")].astype(np.int64)
    local = np.zeros(len(group_of), np.int64)
    for gi in range(len(groups)):
        sel = group_of == gi
        local[sel] = np.arange(int(sel.sum()))
    return group_of, local


def fit_positions(groups: list, group_of: np.ndarray, chunk_docs: int) -> np.ndarray:
    """Each slot's place in the batch its joint model is fitted with.

    The worker cold-fits a slice's docs in chunks of `chunk_docs`, cut in
    creation order (`BrainWorker._run_slow_chunks`); inside a chunk the
    judge gathers the jobs of one model kind and metric count, in the
    chunk's order, into ONE fit batch (`MultivariateJudge.judge` ->
    `_judge_lstm`). So the place is the slot's rank among the docs of its
    own (kind, metric count) inside its chunk. A one-group fleet reads
    `slot % chunk_docs`. Slices hold whole chunks (the fleet checks), so
    chunks are cut at multiples of `chunk_docs` from the fleet's start."""
    slots = len(group_of)
    chunk = np.arange(slots, dtype=np.int64) // int(chunk_docs)
    classes: dict = {}
    for gi, g in enumerate(groups):
        classes.setdefault((g["kind"], len(g["aliases"])), []).append(gi)
    pos = np.zeros(slots, np.int64)
    for members in classes.values():
        sel = np.flatnonzero(np.isin(group_of, members))
        c = chunk[sel]
        pos[sel] = np.arange(len(sel)) - np.searchsorted(c, c, side="left")
    return pos


def draw_sweep(seed: int, sweep: int, groups: list, layout, w: int, n_hist: int,
               fam: dict, tr: dict):
    """Everything one sweep sends: each group's windows [n, F, w] with the
    sweep's spikes applied, the spikes, and each group's baseline windows
    [n, F, points] (None for a group with no `baseline_window`). A canary
    group's docs that really differ from their baseline this sweep have
    their current window moved by the group's `shift`."""
    group_of, local = layout
    values, bases = [], []
    for gi, g in enumerate(groups):
        n, f = int(g["services"]), len(g["aliases"])
        cur = sweep_windows(seed, sweep, n, f, w, n_hist + sweep, fam, gi)
        bw = g.get("baseline_window")
        if bw:
            bases.append(sweep_windows(
                seed, sweep, n, f, int(bw["points"]), n_hist + sweep, fam, gi, STREAM_BASELINE
            ))
            differ = shifted_docs(seed, sweep, gi, n, bw.get("shift_share", 0.0))
            cur[differ] += np.float32(bw.get("shift", 0.0))
        else:
            bases.append(None)
        values.append(cur)
    nwin = [len(g["aliases"]) for g in groups]
    spikes = sweep_spikes(seed, sweep, len(group_of), lambda s: nwin[group_of[s]], w, tr)
    for s, p, m, j in zip(*spikes):
        apply_spike(values[group_of[s]][local[s]], p, m, j)
    return values, spikes, bases


def sample(seed: int, slots: int, n: int) -> np.ndarray:
    """The services whose every judgment the comparison checks."""
    rng = generator(seed, STREAM_SAMPLE)
    return np.sort(rng.choice(slots, size=min(n, slots), replace=False))
