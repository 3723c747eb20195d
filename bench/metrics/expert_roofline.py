"""Share of its roofline that the held experts' grouped products reach
(device trace, expert layer).

The least time of one expert layer's grouped products over ``tokens``
tokens is the larger of its operations at the bf16 peak and its bytes
at the HBM peak (the family's ``expert_work``: the held pairs' three
SwiGLU products, each held expert's bfloat16 weights read once, the
pairs' rows in and out).  Summed over the window's calls, each request
one prefill of ``batch x prompt`` tokens and ``gen`` decode steps of
``batch`` tokens in every expert layer, it is divided by the device time
of the grouped products.  On a TPU ``jax.lax.ragged_dot`` lowers to a
kernel that the trace's ``XLA Ops`` line names ``ragged-dot-none`` (the
product) after a ``ragged-dot-metadata`` (its tiling); this reader sums
every operation whose name holds ``ragged-dot``.  It reads nothing in a
cell whose family counts no expert work, or where no such operation ran.
"""

from bench import harness

OP = "ragged-dot"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    fam = harness.family(run.cell)
    if not hasattr(fam, "expert_work"):
        return None
    device_s = sum(s for name, s in run.trace.op_s.items() if OP in name)
    h, d = run.host, run.cell.dims
    if not device_s or not h.get("requests"):
        return None

    def least(tokens):
        ops, nbytes = fam.expert_work(d, tokens)
        return max(ops / run.peaks.bf16_flops,
                   nbytes / run.peaks.hbm_bytes_per_s)

    per_request = (least(h["batch"] * h["prompt"])
                   + h["gen"] * least(h["batch"]))
    layers = d.n_layers - d.n_dense
    return 100.0 * h["requests"] * layers * per_request / device_s
