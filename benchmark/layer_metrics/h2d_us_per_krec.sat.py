"""Pipeline: staging + dispatch-issue self-time (the ledger's ``h2d``
stage, which on a state-armed dispatch also holds the host's slot
routing, ``assign_slots``) per thousand records."""
from lib.readers import us_per_krec


def read(ctx):
    return us_per_krec(ctx, "h2d")
