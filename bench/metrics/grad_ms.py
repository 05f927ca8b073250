"""Device time per traced step of the grad phase: the busy time of the ops
under the program's ``phase.grad`` scope (the forward and backward pass of
every agent), innermost scope winning.  Moves ``tokens_per_s``."""

from lib import scopes


def read(ctx):
    phases = scopes.read(ctx)["phases"]
    return phases.get("grad") if phases else None
