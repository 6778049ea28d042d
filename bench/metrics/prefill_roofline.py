"""Kernels: the prefill programs' share of their roofline.

For every wave that ran wholly inside the traced window (the harness's
`engine.serve` span, which carries its width and prompt length), the
least time of its prefill is the larger of its operations over peak
FLOP/s and its bytes over peak HBM bandwidth (`prefill_cost` of the
family: causal attention and every token's projections and experts; the
weights once and the cache written). The share is the sum of least times
over the device time of the prefill programs inside those waves. A
family without `prefill_cost` reads nothing. Moves `serve_p95_ms`: every
request waits for its wave's prefill."""

PROGRAM = "prefill"


def read(run):
    tr, fam, m, pk = run.get("trace"), run["family"], run["model"], run["peaks"]
    if tr is None or not hasattr(fam, "prefill_cost"):
        return None
    runs = sorted(iv for name, ivs in tr.program_runs.items()
                  if PROGRAM in name for iv in ivs)
    least = device = 0.0
    for sp in tr.spans:
        if sp.name != "bench:engine.serve" or sp.start < 0 \
                or sp.end > tr.window_s:
            continue
        f, b = fam.prefill_cost(m, int(sp.stats["prompt_len"]),
                                int(sp.stats["width"]))
        least += max(f / pk["bf16_flops"], b / pk["hbm_bw"])
        device += sum(e - st for st, e in runs
                      if st >= sp.start and e <= sp.end)
    return 100.0 * least / device if device > 0 else None
