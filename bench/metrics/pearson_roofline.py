"""pearson_roofline: the Pearson kernel's share of its roofline over the
traced window: one (K, K) gram over every parameter of the K clients'
models per merge round, against the kernel's device time."""
from bench import flops
from bench.readers import kernel_seconds, roofline_share

PROGRAM, KERNEL = r"merge_device", r"pearson"


def read(run):
    tr, rec = run.get("trace"), run["record"]
    if not tr:
        return None
    merges = sum(r["merge"] for j in rec["jobs"] for r in j["rounds"])
    if merges == 0:
        return None
    secs, _n = kernel_seconds(tr, PROGRAM, KERNEL)
    f, b = flops.pearson(rec["num_clients"], rec["param_count"])
    return roofline_share(merges * f, merges * b, secs, run["peaks"])
