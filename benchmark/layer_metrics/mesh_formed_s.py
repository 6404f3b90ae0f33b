"""From the last device plane's warm-up done to every broker showing a
link to each of the others, on the launcher's clock: the brokers dial
each other on their own heartbeat ticks, only from the side with the
smaller identifier, so the last link waits for a tick of a broker that
was warm before its peer had registered: up to one heartbeat interval,
by the phase of the timers. Nothing where the launcher does not say it
(a deployment without broker links)."""

LAYER = "broker_links"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.window.counters["final"].get("mesh_formed_s")
