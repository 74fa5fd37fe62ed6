"""State: the claim rounds of ``route``, one native call (numpy only on
a host without the library), per thousand records of the window: the
ledger's ``claim`` stage, inside ``route`` and part of its time, for
the records the native resolve pass left, which are fresh keys, full
probe windows and the evictions. A program without the stage, or a
window in which no record was left to the rounds, reports nothing."""
from lib.readers import us_per_krec


def read(ctx):
    return us_per_krec(ctx, "claim")
