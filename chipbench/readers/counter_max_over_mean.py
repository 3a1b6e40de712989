"""Largest over mean of the window's counters whose key starts with
`params["prefix"]` (one counter a member: an expert's token assignments):
1.0 is an even load, the straggler's excess otherwise. None where the
program counts no such member or nothing was counted."""


def read(record: dict, params: dict):
    found = [v for k, v in record["counters"].items() if k.startswith(params["prefix"])]
    if not found or sum(found) <= 0:
        return None
    return max(found) / (sum(found) / len(found))
