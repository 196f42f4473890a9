"""Share, in %, of the window's block-layer rows that went to the Mamba-2
forests (``ssd_scan``, ``ssd_decode``): the increase of the program's
``jax.network.rows.ssm`` counter over that of ``jax.network.rows``."""


def read(run):
    c = run.delta["counters"]
    ssm, rows = c.get("jax.network.rows.ssm"), c.get("jax.network.rows")
    return 100.0 * ssm / rows if ssm is not None and rows else None
