"""The comparison that decides ``correct``: every number compared
stands beside its limit, and a run is correct when each holds."""


def at_most(value, limit):
    return {"value": value, "limit": limit, "ok": bool(value <= limit)}


def at_least(value, limit):
    return {"value": value, "limit": limit, "ok": bool(value >= limit)}


def verdict(numbers):
    return bool(numbers) and all(n["ok"] for n in numbers.values())


def lines(numbers):
    return ["compared {}: {} (limit {}) {}".format(
        k, n["value"], n["limit"], "ok" if n["ok"] else "FAILS")
        for k, n in numbers.items()]
