"""Model FLOP utilization of the window: the operations the requests
served need (``bench.work.request_flops``: every layer and causal
attention, the LM head at the last prompt position in prefill and once
per decoded token), over window seconds x chips x the bf16 peak."""

from bench import work


def read(run):
    h = run.host
    if run.peaks is None or not h.get("requests"):
        return None
    flops = h["requests"] * work.request_flops(
        run.cell.dims, h["batch"], h["prompt"], h["gen"])
    return 100.0 * flops / (h["window_s"] * run.cell.chips
                            * run.peaks.bf16_flops)
