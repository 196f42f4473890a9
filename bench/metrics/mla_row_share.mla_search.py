"""Share, in %, of the window's block-layer rows that went to the
latent-attention forests (``mla_prefill``, ``mla_decode``): the increase of
the program's ``jax.network.rows.mla`` counter over that of
``jax.network.rows``."""


def read(run):
    c = run.delta["counters"]
    mla, rows = c.get("jax.network.rows.mla"), c.get("jax.network.rows")
    return 100.0 * mla / rows if mla is not None and rows else None
