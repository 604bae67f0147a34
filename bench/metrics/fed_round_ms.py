"""fed_round_ms: the window over every round it completed, each job's
set-up included (host clock)."""


def read(run):
    return 1e3 * run["window_s"] / run["attempted"]
