"""Share of the window spent in random-effect coordinate updates."""

from .fe_update_share_pct import share


def read(run):
    return share(run, "random")
