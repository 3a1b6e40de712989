"""From a profiler trace (`.xplane.pb`) to numbers.

Busy time is the union of the intervals in which an operation ran on a
device (the plane's "XLA Ops" line), averaged over the devices that ran
anything; program time is summed by the module's jit name from the "XLA
Modules" line; idle gaps are the complement of the union inside the
traced window, each named by the program's host span that covered most
of it. The program's spans carry wall time and the trace its own clock:
a marker annotation the harness writes at a known wall time ties them.
"""

from __future__ import annotations

import glob
import os
import re
import shutil

SYNC = "chipbench.sync"
NO_SPAN = "host, no stage span"
_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(ivs: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in ivs if e > lo and s < hi]


def module_name(event_name: str) -> str:
    return _ID.sub("", event_name.strip())


def device_planes(pd) -> list:
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")]


def sync_ns(pd) -> float | None:
    """Trace-clock start of the harness's marker annotation."""
    for p in pd.planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name == SYNC:
                    return float(ev.start_ns)
    return None


def reduce(pd, window_ns: tuple | None = None, spans: list | None = None,
           wall_to_ns=None, top: int = 10) -> dict:
    """busy_s, per-module and per-op device seconds, idle gaps."""
    planes = device_planes(pd)
    busy, per_dev_union = [], []
    modules: dict = {}
    ops: dict = {}
    for p in planes:
        op_line = [ln for ln in p.lines if ln.name == "XLA Ops"]
        mod_line = [ln for ln in p.lines if ln.name == "XLA Modules"]
        ivs = []
        for ln in op_line or p.lines:
            for ev in ln.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if window_ns and (e <= window_ns[0] or s >= window_ns[1]):
                    continue
                ivs.append([s, e])
                if op_line:
                    rec = ops.setdefault(ev.name, [0.0, 0])
                    rec[0] += (e - s) / 1e9
                    rec[1] += 1
        for ln in mod_line:
            for ev in ln.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if window_ns and (e <= window_ns[0] or s >= window_ns[1]):
                    continue
                rec = modules.setdefault(module_name(ev.name), [0.0, 0])
                rec[0] += (e - s) / 1e9
                rec[1] += 1
        if not ivs:
            continue
        u = union(ivs)
        if window_ns:
            u = _clip(u, *window_ns)
        per_dev_union.append(u)
        busy.append(sum(e - s for s, e in u) / 1e9)
    n_dev = max(len(busy), 1)
    out = {
        "devices_traced": len(busy),
        "busy_s": sum(busy) / n_dev if busy else 0.0,
        "modules": {k: {"seconds": v[0] / n_dev, "count": v[1] / n_dev} for k, v in modules.items()},
        "device_ops": sorted(
            ([k, v[0] / n_dev] for k, v in ops.items()), key=lambda kv: -kv[1]
        )[:top],
        "idle_gaps": [],
    }
    if per_dev_union and window_ns:
        out["idle_gaps"] = _gaps(per_dev_union[0], window_ns, spans or [], wall_to_ns, top)
    return out


def _gaps(u: list, window_ns: tuple, spans: list, wall_to_ns, top: int) -> list:
    lo, hi = window_ns
    gaps, at = [], lo
    for s, e in u:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    staged = []
    if wall_to_ns is not None:
        for sp in spans:
            stage = (sp.get("args") or {}).get("stage")
            if not stage:
                continue
            s = wall_to_ns(sp["ts"] / 1e6)
            staged.append((s, s + sp["dur"] * 1e3, f"{sp['name']}[{stage}]"))
    named: dict = {}
    for gs, ge in gaps:
        # each instant of a gap goes to the innermost (latest-started)
        # stage span that covers it, and to NO_SPAN where none does
        inside = [(max(s, gs), min(e, ge), s, name) for s, e, name in staged if e > gs and s < ge]
        cuts = sorted({gs, ge, *(x[0] for x in inside), *(x[1] for x in inside)})
        for lo, hi in zip(cuts[:-1], cuts[1:]):
            cover = [x for x in inside if x[0] <= lo and x[1] >= hi]
            name = max(cover, key=lambda x: x[2])[3] if cover else NO_SPAN
            named[name] = named.get(name, 0.0) + (hi - lo) / 1e9
    ranked = sorted(([k, v] for k, v in named.items()), key=lambda kv: -kv[1])
    kept = ranked[:top]
    # the part no stage span names is kept whatever its rank: a reader of
    # it must never read 0.0 because it fell off the list
    if NO_SPAN in named and all(k != NO_SPAN for k, _ in kept):
        kept[-1] = [NO_SPAN, named[NO_SPAN]]
    return kept


def reduce_run(ctx, record: dict, on_chip: bool) -> dict:
    """The reduction of one traced run, written beside the run's record."""
    path = find_xplane(ctx.trace_dir) if ctx.trace_dir else None
    empty = {"busy_s": None, "window_s": None, "modules": {}, "breakdown": None}
    if path is None:
        return empty
    pd = load(path)
    t0, t1 = ctx.window_wall
    anchor = sync_ns(pd)
    window_ns = wall_to_ns = None
    if anchor is not None:
        def wall_to_ns(wall_s: float) -> float:
            return anchor + (wall_s - ctx.sync) * 1e9

        window_ns = (wall_to_ns(t0), wall_to_ns(t1))
    red = reduce(pd, window_ns, record.get("spans"), wall_to_ns)
    red["window_s"] = t1 - t0
    red["xplane_bytes"] = os.path.getsize(path)
    # the reduction is what is kept: a raw trace is tens of MB a run
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    red["breakdown"] = {
        "device_ops": [[k, v["seconds"]] for k, v in sorted(
            red["modules"].items(), key=lambda kv: -kv[1]["seconds"])][:10]
        or red["device_ops"],
        "idle_gaps": red["idle_gaps"],
    }
    if not on_chip:
        red["busy_s"] = None
    return red
