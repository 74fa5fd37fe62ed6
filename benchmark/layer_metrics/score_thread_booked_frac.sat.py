"""Pipeline: share of the score thread's time that some stage of it
books (``obs/attr.STAGE_THREADS``: drain, encode, route, shard, h2d,
queue_wait, readback, unshard, sink, commit, prof_sample; ``shard`` and
``unshard`` fire over a mesh alone); what is left is host time of that
thread no span covers. Over the time between the two
snapshots the deltas come from (see ``device_wait_frac.sat``). A stage
that never fired counts as zero; a program that has no ``drain`` stage
does not book the whole thread and reports nothing."""
from lib.readers import stage_delta

SCORE_THREAD = ("drain", "encode", "route", "shard", "h2d", "queue_wait",
                "readback", "unshard", "sink", "commit", "prof_sample")


def read(ctx):
    between = float(ctx["snap1"]["ts"]) - float(ctx["snap0"]["ts"])
    if stage_delta(ctx, "drain") is None or between <= 0:
        return None
    parts = [stage_delta(ctx, s) for s in SCORE_THREAD]
    return 100.0 * sum(p[0] for p in parts if p) / between
