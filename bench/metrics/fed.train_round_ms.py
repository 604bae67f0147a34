"""fed.train_round_ms: mean ``RoundRecord.wall_s`` of the rounds that do
not merge: a scan segment's wall time over its rounds, from dispatch to
its losses on the host (the program's own span)."""


def read(run):
    walls = [r["wall_s"] for j in run["record"]["jobs"] for r in j["rounds"]
             if not r["merge"]]
    return 1e3 * sum(walls) / len(walls) if walls else None
