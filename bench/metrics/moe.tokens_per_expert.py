"""Experts: tokens each held expert gets per decode step, from the
counters of the program's `engine.wave` spans: the sum of `held_slots`
(token x top-k slots of the decode steps, over the expert layers, that
chose an expert held on this chip) over the experts the chip holds (the
held experts of every expert layer) times the sum of `steps`. How near
the held experts come to a deployment's load per expert (there every
chip's batch reaches them). A model without an expert layer, or a
program that keeps no such counter, reads nothing. Moves
`serve_p95_ms`: the decode step reads each expert a token reaches,
whatever the tokens per expert."""


def read(run):
    m = run["model"]
    moe = m.get("moe") or {}
    held = (moe.get("num_experts_held") or moe.get("num_experts") or 0) \
        * (m["num_layers"] - m.get("first_k_dense", 0))
    slots = steps = 0
    for e in run.get("events", ()):
        if e[1] == "span" and e[4].get("name") == "engine.wave" \
                and "held_slots" in e[4]:
            slots += e[4]["held_slots"]
            steps += e[4]["steps"]
    return slots / (held * steps) if held and steps else None
