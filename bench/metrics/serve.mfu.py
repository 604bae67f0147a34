"""serve.mfu: model FLOPs of the window's prompts and decoded tokens (every
matmul, the head at the prompt's last position and at each decoded token,
and causal attention), over the window times the chip's bf16 peak."""
from bench import flops


def read(run):
    rec = run["record"]
    f = flops.serve_flops(rec["model"], rec["prompts_in_window"],
                          rec["decode_lengths"])
    return 100.0 * f / (run["window_s"] * run["peaks"]["bf16_flops_per_s"])
