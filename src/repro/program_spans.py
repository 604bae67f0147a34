"""Names of the program's own profiler spans.

The round engine, the simulator and the serving engine mark their layers
with ``jax.profiler.TraceAnnotation``s under these names, with counters as
keyword arguments. The benchmark reads them from a traced run; a traced
run that did a span's work and holds no span of a name listed here is an
error there, so rename a span here and where it is opened together.
"""

FED = (
    "fed.upload_shards",  # core/federation.FederatedSimulator._upload_shards
    "fed.segment",        # core/engine.RoundEngine._run_segment
    "fed.eval",           # every eval_fn call of the round engine
    "fed.merge_round",    # core/engine.RoundEngine._run_merge_round
    "fed.merge_program",  # in it: the merge program until its plan is on the host
    "fed.merge_host",     # in it: groups, plan, _merge_bookkeeping
)
SERVE = (
    "serve.admit",        # serving/engine.ServeEngine.try_admit
    "serve.step",         # serving/engine.ServeEngine.step
    "serve.evict",        # in it: the eviction loop
)
NAMES = FED + SERVE
