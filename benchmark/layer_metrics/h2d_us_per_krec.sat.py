"""Pipeline: staging + dispatch-issue self-time (the ledger's ``h2d``
stage: ``device_put`` of the encoded payload and the launch; the
host's slot routing has been the ``route`` stage since PR 24) per
thousand records."""
from lib.readers import us_per_krec


def read(ctx):
    return us_per_krec(ctx, "h2d")
