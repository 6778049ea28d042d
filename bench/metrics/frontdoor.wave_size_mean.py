"""FrontDoor: mean number of requests in the waves it dispatched inside the
window, from the runtime's `serve_wave` events (`repro.core.profiler`'s
counter). Moves `serve_p95_ms`: a request that shares a wave waits for
its longest budget."""


def read(run):
    sizes = [e[4].get("size", 0) for e in run.get("events", ())
             if e[1] == "serve_wave"]
    return sum(sizes) / len(sizes) if sizes else None
