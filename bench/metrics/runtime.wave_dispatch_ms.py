"""Runtime: mean, over the waves served in the window, of the hand-off from
the FrontDoor to the replica actor: the start of the program's
`replica.serve_wave` span (in the actor's thread) less the end of the
`frontdoor.queued` spans of its requests (stamped just before the wave's
compiled graph is executed), joined by request id. Moves `serve_p95_ms`:
every request of a wave waits for it."""


def read(run):
    queued, waves = {}, []
    for t, kind, _, _, extra in run.get("events", ()):
        if kind != "span":
            continue
        if extra.get("name") == "frontdoor.queued":
            queued.setdefault(extra["request"], []).append(extra["end"])
        elif extra.get("name") == "replica.serve_wave":
            waves.append((t, extra["requests"]))
    gaps = []
    for start, ids in waves:
        ends = [end for i in ids for end in queued.get(i, ()) if end <= start]
        if ends:
            gaps.append(start - max(ends))
    return sum(gaps) / len(gaps) * 1e3 if gaps else None
