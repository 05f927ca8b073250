"""Device time per traced step of the step's metric outputs: the busy time
of the ops under ``phase.metrics`` (the loss mean and the consensus error,
which reads every parameter again), innermost scope winning.  Moves
``tokens_per_s``."""

from lib import scopes


def read(ctx):
    phases = scopes.read(ctx)["phases"]
    return phases.get("metrics") if phases else None
