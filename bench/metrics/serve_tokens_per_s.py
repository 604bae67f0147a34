"""serve_tokens_per_s: output tokens that reached the client inside the
window, over the window (host clock)."""


def read(run):
    return run["record"]["tokens_in_window"] / run["window_s"]
