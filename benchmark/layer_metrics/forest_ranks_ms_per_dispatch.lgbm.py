"""Kernels: device self time of the ops traced under the scope
``fjt.forest.ranks`` (inside ``fjt.forest``) per execution of the
scoring program: the XLA rank-wire twin's gather of each record's rank
codes to every split of every tree, and their compare (``[B, T, S]``).
The Pallas kernel names no such scope."""
from lib.readers import scope_ms_per_dispatch


def read(ctx):
    return scope_ms_per_dispatch(ctx, "fjt.forest.ranks")
