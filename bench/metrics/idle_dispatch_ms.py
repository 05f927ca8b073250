"""Device idle time per traced step while the host was inside the
program's ``repro:dispatch`` span (launching the jitted step: arguments,
donation, transfer of the batch).  Moves ``tokens_per_s``."""

from lib import scopes


def read(ctx):
    idle = scopes.read(ctx)["idle"]
    return idle["dispatch"] if idle else None
