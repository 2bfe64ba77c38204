"""Set-up seconds in XLA compiles or loads from the persistent compile
cache, from the program's build ledger (`utils.jaxtools.build_seconds`,
each instant counted once).  Read after the window, which builds
nothing; a program without the ledger, or a window with no device work,
gives nothing."""


def read(ctx):
    try:
        from consensus_specs_tpu.utils.jaxtools import build_seconds, builds
    except ImportError:
        return None
    # a window that ran nothing on a device, as on the CPU, measured no
    # device program's set-up
    if not builds() or not ctx["trace"].devices:
        return None
    return build_seconds()["compile_s"]
