"""State: the host's slot routing on a state-armed dispatch (the
ledger's ``route`` stage: key hashing, ``maybe_renorm``,
``assign_slots``, the pad rows) per thousand records. A program
without the stage reports nothing."""
from lib.readers import us_per_krec


def read(ctx):
    return us_per_krec(ctx, "route")
