"""Set-up seconds JAX spent tracing and lowering the programs the run
built, from the program's build ledger (`utils.jaxtools.build_seconds`:
JAX's own compile events, each instant counted once).  Read after the
window, which builds nothing; a program without the ledger, or a window
with no device work, gives nothing."""


def read(ctx):
    try:
        from consensus_specs_tpu.utils.jaxtools import build_seconds, builds
    except ImportError:
        return None
    # a window that ran nothing on a device, as on the CPU, measured no
    # device program's set-up
    if not builds() or not ctx["trace"].devices:
        return None
    s = build_seconds()
    return s["trace_s"] + s["lower_s"]
