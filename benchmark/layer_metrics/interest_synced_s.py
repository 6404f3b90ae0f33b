"""From the launcher's answer to the last ``place`` (the last group's
users connect after it) to every broker holding every peer's topics and
every user's home, on the launcher's clock: the last group's connects,
then what the syncs still owe. A join pushes its partial syncs at once
(strong consistency), so this is the connects and a little; without it,
up to one sync interval. Nothing where the launcher does not say it (a
deployment without broker links)."""

LAYER = "broker_links"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.window.counters["final"].get("interest_synced_s")
