"""idle_fetch.backlog: device idle time inside the traced window that the
trace reduction gives to the program's ``vision.fetch`` span (every lane's
result back to the host), as a share (%) of the window.  None where the
trace names no ``vision.*`` span at all (a program without the spans)."""

SPAN = "vision.fetch"


def read(ctx):
    red = ctx["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    idle = dict(red["idle_gaps"])
    if not any(name.startswith("vision.") for name in idle):
        return None
    return 100.0 * idle.get(SPAN, 0.0) / red["window_s"]
