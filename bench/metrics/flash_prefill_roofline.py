"""flash_prefill_roofline: the flash-prefill kernel's share of its roofline
over the traced window: causal attention over every admitted prompt, in
every layer, against the kernel's device time."""
from bench import flops
from bench.readers import kernel_seconds, roofline_share

PROGRAM, KERNEL = r"jit_admit", r"flash_prefill"


def read(run):
    tr, rec = run.get("trace"), run["record"]
    if not tr or not rec["prompts_in_window"]:
        return None
    secs, _n = kernel_seconds(tr, PROGRAM, KERNEL)
    m = rec["model"]
    f = b = 0.0
    for L in rec["prompts_in_window"]:
        fl, by = flops.flash_prefill(L, m["num_attention_heads"],
                                     m["num_key_value_heads"], m["head_dim"])
        f, b = f + fl, b + by
    n_layers = m["num_hidden_layers"]
    return roofline_share(n_layers * f, n_layers * b, secs, run["peaks"])
