"""Pipeline: share of the bucket rows the window's keyed dispatches
offered the chips (``mesh_bucket_slots``: ``D·C`` a dispatch) that held
a record and not padding. 100 where every chip's bucket ran full; the
skew of the keys and the cut at the first full bucket keep it lower."""
from lib.readers import counter_delta


def read(ctx):
    slots = counter_delta(ctx, "mesh_bucket_slots")
    pad = counter_delta(ctx, "mesh_bucket_pad_records")
    if not slots or pad is None:
        return None
    return 100.0 * (slots - pad) / slots
