"""Device time per traced step of the flat-buffer relayout: the busy time
of the ops under ``phase.pack`` and ``phase.unpack`` (the pytree into the
update kernel's packed buckets and back), innermost scope winning.  Moves
``tokens_per_s``."""

from lib import scopes


def read(ctx):
    phases = scopes.read(ctx)["phases"]
    if not phases or not {"pack", "unpack"} & set(phases):
        return None
    return phases.get("pack", 0.0) + phases.get("unpack", 0.0)
