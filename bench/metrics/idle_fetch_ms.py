"""Device idle time per traced step while the host was inside the
program's ``repro:fetch`` span (turning the step's metrics into Python
numbers and logging them).  Moves ``tokens_per_s``."""

from lib import scopes


def read(ctx):
    idle = scopes.read(ctx)["idle"]
    return idle["fetch"] if idle else None
