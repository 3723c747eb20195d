"""Host time of ``ProgressiveServer.decode`` (which ends in a copy of its
tokens to the host) per decode step (host clock, progressive server)."""


def read(run):
    s = run.host.get("decode_s")
    return 1e3 * sum(s) / run.host["decode_steps"] if s else None
