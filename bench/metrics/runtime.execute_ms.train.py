"""Runtime: host milliseconds inside the compiled graph's `execute` call
per training step (the harness's own span around `CompiledGraph.execute`,
`core/dag.py`), over the window's steps. Moves `train_tokens_per_s`."""


def read(run):
    ex = run.get("execute_s")
    return 1e3 * sum(ex) / len(ex) if ex else None
