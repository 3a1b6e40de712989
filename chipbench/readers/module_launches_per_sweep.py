"""Device modules launched in the traced window over its sweeps: how many
programs a sweep dispatches (a slice of a mixed fleet dispatches one a
model kind). None with no trace."""


def read(record: dict, params: dict):
    mods = (record.get("trace") or {}).get("modules") or {}
    sweeps = len(record.get("sweeps") or [])
    launches = sum(v["count"] for v in mods.values())
    if launches <= 0 or not sweeps:
        return None
    return launches / sweeps
