"""Pipeline: the bytes the scoring program's output brings back from
the chip for the sink (the counter ``readback_bytes``, booked once a
completed dispatch) over the records delivered (``records_out``), in the
window. A classifier's triple is 4 + 4·C + 4 bytes a record; a program
that books no such counter reports nothing."""
from lib.readers import counter_delta


def read(ctx):
    got = counter_delta(ctx, "readback_bytes")
    recs = counter_delta(ctx, "records_out")
    return got / recs if got is not None and recs else None
