"""Of the frames the deployment's planes staged, the share that came over
a broker link: the program's counters ``link_frames_staged`` (what
``broker_receive_loop`` staged) over ``frames_staged``, summed over the
brokers by the launcher, between the ``before`` and ``final`` marks.

In a cell whose every broadcast has a subscriber behind every broker, a
broadcast is staged once at its origin and once at each of the ``n - 1``
peers, and a direct to another broker's user at its owner alone: with
four brokers and ``cross-sat``'s nine broadcasts in ten, about 2.8 of 3.7
stagings a frame, 0.76. Nothing where the program has no such counter
(an older commit) or staged nothing."""

LAYER = "broker_links"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "broker_cpu_us_per_delivery"


def read(run):
    marks = run.window.counters
    before, final = marks["before"], marks["final"]
    delta = {}
    for key in ("link_frames_staged", "frames_staged"):
        if before.get(key) is None or final.get(key) is None:
            return None
        delta[key] = final[key] - before[key]
    if not delta["frames_staged"]:
        return None
    return delta["link_frames_staged"] / delta["frames_staged"]
