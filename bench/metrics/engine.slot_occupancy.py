"""Engine: share of the decode slots of the waves that carried a live lane,
from the counters of the program's `engine.wave` spans: 100 x the sum of
`live_lane_steps` (lanes still under their budget, summed over the loop's
iterations) over the sum of `lane_steps` (width x iterations). A lane
that finished rides along masked until the wave's longest budget is
spent. Moves `serve_p95_ms`: masked lanes are decode work that serves no
one."""


def read(run):
    live = lanes = 0
    for e in run.get("events", ()):
        if e[1] == "span" and e[4].get("name") == "engine.wave":
            live += e[4]["live_lane_steps"]
            lanes += e[4]["lane_steps"]
    return 100.0 * live / lanes if lanes else None
