"""Kernels: device self time of the ops traced under the scope
``fjt.forest`` (the Pallas forest kernel and its scan over the
dispatch's chunks) per execution of the scoring program."""
from lib.readers import scope_ms_per_dispatch


def read(ctx):
    return scope_ms_per_dispatch(ctx, "fjt.forest")
