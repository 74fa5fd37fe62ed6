"""Pipeline: the score thread taking its batch off the ring (the
ledger's ``drain`` stage: ``ring.drain`` and the multi-chunk
aggregation's copies) per thousand records. A program without the stage
reports nothing."""
from lib.readers import us_per_krec


def read(ctx):
    return us_per_krec(ctx, "drain")
