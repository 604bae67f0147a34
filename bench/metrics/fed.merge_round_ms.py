"""fed.merge_round_ms: mean ``RoundRecord.wall_s`` of the merge rounds:
training, the Pearson similarity, the device plan, the mix, the host's
shard bookkeeping and the round's evaluation (the program's own span)."""


def read(run):
    walls = [r["wall_s"] for j in run["record"]["jobs"] for r in j["rounds"]
             if r["merge"]]
    return 1e3 * sum(walls) / len(walls) if walls else None
