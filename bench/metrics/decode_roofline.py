"""Kernels: the decode steps' share of their roofline.

For every wave that ran wholly inside the traced window (the harness's
`engine.serve` span, which carries its width, prompt length and budgets),
the least time of each decode step is the larger of its operations over
peak FLOP/s and its bytes over peak HBM bandwidth (`decode_cost` of the
family: weights once, the K/V or recurrent state of the positions each
live lane attends to, the new token's writes; never the cache's
capacity). The share is the sum of least times over the device time of
the decode programs inside those waves. Moves `serve_p95_ms`: every
output token waits one decode step."""

PROGRAM = "decode_step"


def read(run):
    tr, fam, m, pk = run.get("trace"), run["family"], run["model"], run["peaks"]
    if tr is None:
        return None
    runs = sorted(iv for name, ivs in tr.program_runs.items()
                  if PROGRAM in name for iv in ivs)
    least = device = 0.0
    for sp in tr.spans:
        if sp.name != "bench:engine.serve" or sp.start < 0 \
                or sp.end > tr.window_s:
            continue
        width, s = int(sp.stats["width"]), int(sp.stats["prompt_len"])
        budgets = [int(b) for b in str(sp.stats["budgets"]).split(",")]
        for j in range(max(budgets) - 1):
            live = [s + j + 1 for b in budgets if b > j + 1]
            f, b = fam.decode_cost(m, live, width)
            least += max(f / pk["bf16_flops"], b / pk["hbm_bw"])
        device += sum(e - st for st, e in runs
                      if st >= sp.start and e <= sp.end)
    return 100.0 * least / device if device > 0 else None
