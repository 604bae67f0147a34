"""paged_decode_roofline: the paged decode-attention kernel's share of its
roofline over the traced window: every decoded row's attention over its
cache, in every layer, against the kernel's device time."""
from bench import flops
from bench.readers import kernel_seconds, roofline_share

PROGRAM, KERNEL = r"jit_step", r"paged"


def read(run):
    tr, rec = run.get("trace"), run["record"]
    if not tr or not rec["decode_lengths"]:
        return None
    secs, _n = kernel_seconds(tr, PROGRAM, KERNEL)
    m = rec["model"]
    f, b = flops.paged_decode(rec["decode_lengths"], m["num_attention_heads"],
                              m["num_key_value_heads"], m["head_dim"])
    L = m["num_hidden_layers"]
    return roofline_share(L * f, L * b, secs, run["peaks"])
