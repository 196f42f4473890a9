"""Share, in %, of the forest rows of the window's network calls that only
fill the row buckets: the increase of the program's
``jax.network.pad_rows`` counter over that of all rows sent
(``jax.network.rows`` + ``jax.network.pad_rows``)."""


def read(run):
    c = run.delta["counters"]
    pad, rows = c.get("jax.network.pad_rows"), c.get("jax.network.rows")
    return 100.0 * pad / (rows + pad) if pad is not None and rows else None
