"""Model step: model operations of the requests served in the window
(each prompt's prefill, then one token's forward per further output token
at its context), over window seconds x chips x peak bf16 FLOP/s. Counts
come from shapes (`models/<family>.py`), never from the program. Moves
`serve_p95_ms`."""


def read(run):
    fam, m = run["family"], run["model"]
    served = run.get("served_in_window") or []
    if not served:
        return None
    flops = 0.0
    for r in served:
        s = len(r.prompt)
        flops += fam.prefill_flops(m, s)
        flops += sum(fam.token_flops(m, s + k) for k in range(1, len(r.tokens)))
    return 100.0 * flops / (run["seconds"] * run["chips"]
                            * run["peaks"]["bf16_flops"])
