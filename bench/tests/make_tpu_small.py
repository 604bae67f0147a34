#!/usr/bin/env python3
"""Records the small TPU trace the span tests read
(``bench/tests/data/tpu_small.xplane.pb``).

    python bench/tests/make_tpu_small.py [OUT]

Inside a ``bench.window`` span, with the Python tracer off: one job of a small federation through
its merge round on the compiled round engine (cnn_mnist, 10 clients,
3 rounds merging at round 1, the Pearson kernel), then a paged serving
engine (qwen3-1.7b's widths in bf16 with 2 of its layers) admitting four
requests and decoding them to the end (flash-prefill and paged-decode
kernels). Both run once before the window, so the trace holds no
compilation. Of the HLO protos the profiler embeds (one per compiled
program, most of the file) only those ``bench/scopes.py`` reads are kept,
each cut to the name and op_name of the instructions that ran on the
device; this needs TensorFlow's XPlane and HLO protos. On a CPU the same
runs at the reduced serving config, to rehearse the script.
"""
from __future__ import annotations

import dataclasses
import glob
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

OUT = ROOT / "bench" / "tests" / "data" / "tpu_small.xplane.pb"


SPEC = dict(num_clients=10, n_train=1200, n_test=200, rounds=3, merge_at=(1,),
            local_epochs=1, steps_per_epoch=2, batch_size=16,
            pipeline="engine", seed=0)


def federation(programs=None):
    """One job; returns its programs (shared with the next job, as the
    benchmark shares them) and the groups each round merged."""
    from repro.core.engine import RoundEngine
    from repro.launch.experiment import ExperimentSpec, build_simulator

    eng = RoundEngine(build_simulator(ExperimentSpec(**SPEC)), programs=programs)
    hist = eng.run()
    return eng.programs, [list(map(list, r.merged_groups)) for r in hist]


def serving_engine():
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serving import ServeEngine
    from repro.serving.fl_model import serve_config

    if jax.default_backend() == "tpu":
        cfg = dataclasses.replace(get_config("qwen3-1.7b"), num_layers=2)
    else:
        cfg = serve_config("qwen3-1.7b")
    params = jax.jit(M.init_params, static_argnums=1)(jax.random.PRNGKey(0), cfg)
    return cfg, ServeEngine(params, cfg, num_slots=4, capacity=256,
                            kv_layout="paged", block_size=16)


def serve(cfg, eng):
    from repro.serving.traffic import Request

    rng = np.random.default_rng(0)
    for i, (n, new) in enumerate([(128, 8), (64, 12), (128, 4), (64, 6)]):
        eng.try_admit(Request(rid=i, client_id=0, max_new_tokens=new,
                              prompt=rng.integers(0, cfg.vocab_size, n)
                              .astype(np.int32)))
    steps = 0
    while eng.num_active:
        eng.step()
        steps += 1
    return steps


def cut_hlo_protos(path: Path):
    """Keeps the ``/host:metadata`` plane's HLO protos of the programs in
    ``bench.scopes.SCOPES`` and drops the others; a kept proto holds only
    the name and op_name of each instruction that ran as a device
    operation."""
    from jax.profiler import ProfileData
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from bench.scopes import SCOPES
    from bench.trace import OPS_LINE, _module_name, _short_op

    data = path.read_bytes()
    ran = {_short_op(e.name)
           for p in ProfileData.from_serialized_xspace(data).planes
           if p.name.startswith("/device:")
           for ln in p.lines if ln.name == OPS_LINE for e in ln.events}
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(data)
    for p in xs.planes:
        if p.name != "/host:metadata":
            continue
        for key in list(p.event_metadata):
            meta = p.event_metadata[key]
            if _module_name(meta.name) not in SCOPES or not meta.stats:
                del p.event_metadata[key]
                continue
            full = hlo_pb2.HloProto()
            full.ParseFromString(meta.stats[0].bytes_value)
            cut = hlo_pb2.HloProto()
            for comp in full.hlo_module.computations:
                kept = [i for i in comp.instructions if i.name in ran]
                if kept:
                    c = cut.hlo_module.computations.add()
                    for i in kept:
                        ins = c.instructions.add()
                        ins.name = i.name
                        ins.metadata.op_name = i.metadata.op_name
            meta.stats[0].bytes_value = cut.SerializeToString()
    path.write_bytes(xs.SerializeToString())


def main() -> int:
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else OUT
    cfg, eng = serving_engine()
    programs, _ = federation()
    serve(cfg, eng)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        _, groups = federation(programs)
        steps = serve(cfg, eng)
        jax.effects_barrier()
    jax.profiler.stop_trace()
    src = max(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    cut_hlo_protos(out)
    print(f"{out}: {out.stat().st_size} bytes on {jax.devices()[0].device_kind}; "
          f"merge groups {groups}, {steps} decode steps")
    return 0


if __name__ == "__main__":
    sys.exit(main())
