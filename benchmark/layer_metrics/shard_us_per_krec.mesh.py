"""Pipeline: the keyed shuffle's host time per thousand records of the
window: the ledger's ``shard`` (owner and rank of each held record, the
cut, the bucketed operands, the codes put in bucket order) plus
``unshard`` (scores fetched and put back in offset order before the
sink). A program without the shuffle books neither and reports
nothing."""
from lib.readers import stage_delta, us_per_krec


def read(ctx):
    if stage_delta(ctx, "shard") is None:
        return None
    stages = [s for s in ("shard", "unshard") if stage_delta(ctx, s)]
    return us_per_krec(ctx, *stages)
