"""From the first connect command to every client process reporting all its
users connected (marshal auth, permit, broker handshake, per user)."""

LAYER = "marshal_auth"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.setup.get("connect_s")
