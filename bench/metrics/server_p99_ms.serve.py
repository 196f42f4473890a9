"""The service's own p99 of its ``predict`` endpoint, in ms, from the
``stats`` op after the window (a sliding window of its last requests)."""


def read(run):
    ep = run.stats.get("server_after", {}).get("metrics", {}).get("endpoints", {}).get("predict")
    return ep["p99_ms"] if ep and ep.get("p99_ms") is not None else None
