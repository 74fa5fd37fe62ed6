"""Kernels: device self time of the state fold (the scopes
``fjt.fold.gather`` and ``fjt.fold.scatter``: the reset, the gather and
the whole-row and column-sliced scatters) per execution of the scoring
program. With ``forest_ms_per_dispatch.sat`` it adds up to
``program_ms_per_dispatch.sat`` less what runs under no scope."""
from lib.readers import scope_ms_per_dispatch


def read(ctx):
    return scope_ms_per_dispatch(ctx, "fjt.fold.gather", "fjt.fold.scatter")
