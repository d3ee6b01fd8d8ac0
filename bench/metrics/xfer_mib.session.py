"""Host-device MiB per session query: ``h2d_bytes + d2h_bytes`` of
``engine.last_exec_stats`` after each persist, summed over the query,
averaged over the units."""


def read(ctx):
    if ctx["loop"] != "session" or not ctx["units"]:
        return None
    return sum(u["xfer_bytes"] for u in ctx["units"]) \
        / len(ctx["units"]) / 2 ** 20
