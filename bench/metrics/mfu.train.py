"""Model step: forward and backward model operations per token (three
forwards' worth, from shapes; recomputation does not count) times the
tokens of the steps completed in the window, over window seconds x chips
x peak bf16 FLOP/s. Moves `train_tokens_per_s`."""


def read(run):
    tokens = run.get("window_tokens")
    if not tokens:
        return None
    fam, m = run["family"], run["model"]
    per = fam.train_flops_per_token(m, int(run["traffic"]["seq_len"]))
    return 100.0 * per * tokens / (run["seconds"] * run["chips"]
                                   * run["peaks"]["bf16_flops"])
