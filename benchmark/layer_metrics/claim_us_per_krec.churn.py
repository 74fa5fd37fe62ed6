"""State: the numpy claim rounds of ``route`` (the ledger's ``claim``
stage, inside ``route`` and part of its time: the records the native
pass left, which are fresh keys, full probe windows and the evictions)
per thousand records of the window. A program without the stage, or a
window in which no record was left to the rounds, reports nothing."""
from lib.readers import us_per_krec


def read(ctx):
    return us_per_krec(ctx, "claim")
