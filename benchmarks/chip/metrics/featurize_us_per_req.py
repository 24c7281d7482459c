"""Host time hashing and encoding programs per request in the window
(the service's ``phase_stats`` hash_s + encode_s over requests)."""


def read(ctx, metric):
    c = ctx["outcome"].layers["counts"]
    if not c["requests"]:
        return None
    return 1e6 * (c["hash_s"] + c["encode_s"]) / c["requests"]
