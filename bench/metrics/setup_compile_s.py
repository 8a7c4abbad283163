"""setup_compile_s: seconds the program spent in backend compiles or
compile-cache loads from its start to the end of the window, the sum over
functions of its ``jax_compile_seconds_total`` counter: the compile share
of set-up, plus any compile inside the window.  None where the program
keeps no such counter."""


def read(ctx):
    from repro.obs import metrics
    snap = metrics.snapshot().get("jax_compile_seconds_total")
    if snap is None:
        return None
    return sum(row["value"] for row in snap["values"])
