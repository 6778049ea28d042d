"""Engine: host milliseconds per decode step that the host did not spend
blocked on a token, from the program's `engine.wave` spans: the sum of
(`decode_s` - `sync_s`) over the waves, over the sum of their `steps`
(decode programs dispatched). `decode_s` runs from the first token's pull
to the wave's end, `sync_s` is the time blocked in the later pulls. Moves
`serve_p95_ms`: while the host works between steps the chip may idle."""


def read(run):
    host = steps = 0
    for e in run.get("events", ()):
        if e[1] == "span" and e[4].get("name") == "engine.wave":
            host += e[4]["decode_s"] - e[4]["sync_s"]
            steps += e[4]["steps"]
    return host / steps * 1e3 if steps else None
