"""Engine: device milliseconds per decode step, from the trace.

The engine's decode jit wraps `Model.decode_step`, so its program is named
`jit_decode_step`; this reader sums the device time of the programs whose
name holds PROGRAM, over how many ran. Moves `serve_p95_ms`: every token
of every lane waits one step."""

PROGRAM = "decode_step"


def read(run):
    tr = run.get("trace")
    if tr is None:
        return None
    s, n = tr.program_time(PROGRAM)
    return s / n * 1e3 if n else None
