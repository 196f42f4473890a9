"""Share of the window's lookups the result cache answered, in percent:
hits over hits and misses, from the ``stats`` op before and after."""


def read(run):
    a = run.stats.get("server_after", {}).get("result_cache")
    b = run.stats.get("server_before", {}).get("result_cache")
    if not a or not b:
        return None
    hits = a["hits"] - b["hits"]
    total = hits + a["misses"] - b["misses"]
    return hits / total * 100.0 if total else None
