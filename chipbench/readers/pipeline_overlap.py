"""Share of the sweep pipeline's stage-busy time hidden by overlap, over
the window's sweeps: 1 - wall / (fetch + judge + write). Source: the
worker's per-sweep pipeline stats (`_last_sweep`, the overlap gauge)."""


def read(record: dict, params: dict):
    busy = wall = 0.0
    for s in record["sweeps"]:
        p = (s.get("last_sweep") or {}).get("pipeline")
        if not p:
            continue
        busy += p["fetch_seconds"] + p["judge_seconds"] + p["write_seconds"]
        wall += p["wall_seconds"]
    if busy <= 0.0 or wall <= 0.0:
        return None
    return 100.0 * max(0.0, 1.0 - wall / busy)
