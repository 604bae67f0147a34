"""fed.job_setup_ms: mean time to build one job, the simulator with its
client shards uploaded and the round engine around it (the benchmark's
span around ``FederatedSimulator(...)`` and ``RoundEngine(...)``)."""


def read(run):
    jobs = run["record"]["jobs"]
    return 1e3 * sum(j["setup_s"] for j in jobs) / len(jobs)
