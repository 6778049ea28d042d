"""FrontDoor: 95th percentile, over the requests admitted in the window, of
how long each waited in the queue, from the program's `frontdoor.queued`
spans (`repro.core.profiler`): admission to the dispatch of the request's
wave, or to its shedding. Moves `serve_p95_ms`: a request's latency is
its wait in the queue plus its wave's service."""
import numpy as np


def read(run):
    waits = [(e[4]["end"] - e[0]) * 1e3 for e in run.get("events", ())
             if e[1] == "span" and e[4].get("name") == "frontdoor.queued"]
    return float(np.percentile(waits, 95)) if waits else None
