"""fed.mfu: forward and backward FLOPs of every sample the active clients
trained on in the window, over the window times the chip's bf16 peak (the
CNN's float32 matmuls run as one bf16 pass at the default precision)."""


def read(run):
    rec = run["record"]
    samples = sum(r["active"] for j in rec["jobs"] for r in j["rounds"]) \
        * rec["samples_per_client_round"]
    flops = samples * rec["train_flops_per_sample"]
    return 100.0 * flops / (run["window_s"] * run["peaks"]["bf16_flops_per_s"])
