"""Share of its roofline that the layered head reaches (device trace).

The least time of one head call, the larger of its operations at the
bf16 peak and its bytes at the HBM peak (``bench.work.head_work``: the
m int8 planes read once, the bfloat16 hidden, the m float32
resolutions), times the decode steps of the window, over the device
time of the head's program.  The head is the only program of the serve
path that ``ProgressiveServer`` jits from a lambda, so the trace names
it ``jit__lambda``; until the program gives it a stable name of its own
this reader looks for that one, and reads nothing where it is absent.
"""

from bench import work

MODULE = "jit__lambda"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    device_s = run.trace.module_s.get(MODULE)
    if not device_s:
        return None
    d, h = run.cell.dims, run.host
    ops, nbytes = work.head_work(h["batch"], d.d_model, d.vocab,
                                 run.cell.traffic["head_m"])
    least = max(ops / run.peaks.bf16_flops,
                nbytes / run.peaks.hbm_bytes_per_s)
    return 100.0 * least * h["decode_steps"] / device_s
