"""Mean host time of ``ProgressiveServer.prefill`` per request, ending in
``block_until_ready`` (host clock, progressive server)."""


def read(run):
    s = run.host.get("prefill_s")
    return 1e3 * sum(s) / len(s) if s else None
