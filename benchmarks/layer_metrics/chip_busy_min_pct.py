"""Device: busy share of the traced stretch on the IDLEST chip
(`chip_busy_max_pct`'s arithmetic, the smallest of the planes): how far
the mesh shares the work.  Near 0 beside a busy `chip_busy_max_pct`: one
chip does the relational work and the others only take part in the
exchange programs."""

from layer_metrics.chip_busy_max_pct import chip_busy_pcts


def read(ctx):
    shares = chip_busy_pcts(ctx)
    return min(shares) if shares else None
