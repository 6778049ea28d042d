"""Kernels: share of the blockwise prefill attention's blocks that are
computed, from the counters of the program's `engine.wave` spans: 100 x
the sum of `prefill_attn_blocks_run` over the sum of
`prefill_attn_blocks` (the (query chunk, KV chunk) blocks its prefills
form, over lanes and layers). A block no query-key pair of which
survives the mask is skipped. A run whose prefills take no blockwise
attention, or a program that keeps no such counter, reads nothing. Moves
`serve_p95_ms`: each block computed is a score product, its softmax
passes and a weights-times-V product."""


def read(run):
    blocks = computed = 0
    for e in run.get("events", ()):
        if e[1] == "span" and e[4].get("name") == "engine.wave" \
                and "prefill_attn_blocks" in e[4]:
            blocks += e[4]["prefill_attn_blocks"]
            computed += e[4]["prefill_attn_blocks_run"]
    return 100.0 * computed / blocks if blocks else None
