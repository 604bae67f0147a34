"""FL training driver — the spec CLI over the declarative experiment API.

Every run is an :class:`repro.launch.experiment.ExperimentSpec`; the CLI
only builds (or loads) a spec and hands it to ``run_experiment``. Choices
are registry-driven, so a newly registered scenario/policy/model shows up
here without touching this file.

  PYTHONPATH=src python -m repro.launch.train --scenario normal --rounds 10
  PYTHONPATH=src python -m repro.launch.train --scenario adverse --aggregator trimmed
  PYTHONPATH=src python -m repro.launch.train --merge-policy cosine --merge-at 2 5
  PYTHONPATH=src python -m repro.launch.train --spec experiments/fl/run.spec.json
  PYTHONPATH=src python -m repro.launch.train --dump-spec   # print + exit

Writes per-round history JSON + a final global-model checkpoint + the
spec sidecar (``<tag>.spec.json``) that reproduces the run.
"""
from __future__ import annotations

import argparse
import json
import os

from repro.checkpoint import save_pytree
from repro.core.merge_policy import MERGE_POLICIES
from repro.core.scenarios import SCENARIOS
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.experiment import (
    AGGREGATORS,
    ALGORITHMS,
    ExperimentSpec,
    FL_DATASETS,
    FL_MODELS,
    MESHES,
    run_experiment,
)


def spec_from_args(args: argparse.Namespace) -> ExperimentSpec:
    if args.spec:
        with open(args.spec) as f:
            return ExperimentSpec.from_json(f.read())
    return ExperimentSpec(
        model=args.model,
        dataset=args.dataset,
        n_train=args.n_train,
        n_test=args.n_test,
        num_clients=args.clients,
        algo=args.algo,
        aggregator=args.aggregator,
        merge=not args.no_merge,
        merge_policy=args.merge_policy,
        merge_at=tuple(args.merge_at),
        threshold=args.threshold,
        corr_sample=args.corr_sample,
        block_size=args.block_size,
        sketch_dim=args.sketch_dim,
        scenario=args.scenario,
        rounds=args.rounds,
        local_epochs=args.local_epochs,
        steps_per_epoch=args.steps_per_epoch,
        pipeline=args.pipeline,
        mesh=None if args.mesh == "none" else args.mesh,
        seed=args.seed,
    )


def main():
    ap = argparse.ArgumentParser(
        description="Run one FL experiment from a declarative spec."
    )
    ap.add_argument("--spec", default=None,
                    help="load an ExperimentSpec JSON (overrides all other "
                         "spec flags)")
    ap.add_argument("--model", default="cnn_mnist", choices=FL_MODELS.names())
    ap.add_argument("--dataset", default="synthetic_mnist",
                    choices=FL_DATASETS.names())
    ap.add_argument("--scenario", default="normal", choices=SCENARIOS.names())
    ap.add_argument("--algo", default="scaffold", choices=ALGORITHMS)
    ap.add_argument("--aggregator", default="mean", choices=AGGREGATORS)
    ap.add_argument("--merge-policy", default="pearson",
                    choices=MERGE_POLICIES.names())
    ap.add_argument("--no-merge", action="store_true")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--merge-at", type=int, nargs="+", default=[4],
                    help="rounds on which the merge policy runs")
    ap.add_argument("--threshold", type=float, default=0.7)
    ap.add_argument("--corr-sample", type=int, default=0,
                    help="correlate over a random coordinate subsample "
                         "(0 = all params), fused into the streaming path")
    ap.add_argument("--block-size", type=int, default=0,
                    help="pearson-blocked: pod size for blocked "
                         "hierarchical planning (0 = flat, one block)")
    ap.add_argument("--sketch-dim", type=int, default=0,
                    help="pearson-blocked: similarity-sketch dimension "
                         "(0 = exact streaming tree-Pearson)")
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--n-train", type=int, default=6000)
    ap.add_argument("--n-test", type=int, default=1000)
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--steps-per-epoch", type=int, default=10)
    ap.add_argument("--pipeline", default="device",
                    choices=["device", "host", "engine"],
                    help="round pipeline: zero-copy streaming per-round "
                         "(device), the numpy oracle (host), or the "
                         "compiled scan-over-rounds engine (engine)")
    ap.add_argument("--mesh", default="none",
                    choices=["none"] + MESHES.names(),
                    help="named mesh for the pod-sharded mode (default: none)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="experiments/fl")
    ap.add_argument("--dump-spec", action="store_true",
                    help="print the resolved spec JSON and exit")
    args = ap.parse_args()

    spec = spec_from_args(args)
    if args.dump_spec:
        print(spec.to_json())
        return
    print(spec.describe())

    enable_compile_cache()
    sim, hist = run_experiment(spec)
    os.makedirs(args.out, exist_ok=True)
    tag = (f"{spec.scenario}__{spec.algo}__"
           f"{spec.merge_policy if spec.merge else 'nomerge'}")
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump([r.__dict__ for r in hist], f, indent=2, default=str)
    with open(os.path.join(args.out, tag + ".spec.json"), "w") as f:
        f.write(spec.to_json())
    save_pytree(os.path.join(args.out, tag + ".npz"), sim.params)
    print(f"final accuracy: {hist[-1].accuracy:.4f} -> {args.out}/{tag}.json")


if __name__ == "__main__":
    main()
