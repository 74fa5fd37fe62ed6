"""Pipeline: host rank-encode self-time per thousand records."""
from lib.readers import us_per_krec


def read(ctx):
    return us_per_krec(ctx, "encode")
