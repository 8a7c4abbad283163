"""fetch_gbps.detect: result bytes the engine copied to the host in the
window over the host seconds of its fetch phase, in GB/s: the sums over
the window's engine calls of ``last_stats`` ``d2h_bytes`` and ``fetch_s``
(host clock).  How near the fetch of a detector's three maps runs to the
host link.  None where the program counts no ``d2h_bytes``."""


def read(ctx):
    st = ctx["stats"]
    if not st.get("d2h_bytes") or not st.get("fetch_s"):
        return None
    return st["d2h_bytes"] / st["fetch_s"] / 1e9
