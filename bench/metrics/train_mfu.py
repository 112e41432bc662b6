"""Share of the chip's bf16 peak that the window's train steps needed: the
operations a forward and backward pass need per token (the configuration's
reference counts them from its shapes), times the tokens of the window's
steps, over the window times the peak of the chip's ``device_kind``."""


def read(run):
    c, peak = run["counters"], run["peaks"]
    if not c.get("tokens") or peak is None:
        return None
    return 100.0 * c["tokens"] * c["flops_per_token"] / (c["window_s"] * peak["bf16_flops_per_s"])
