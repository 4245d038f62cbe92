"""compile_s: seconds XLA spent compiling and reading executables from the
persistent cache during one restart (JAX's monitoring events); mean over
the window's restarts.  Moves ``restart_s``."""


def read(rec):
    return rec["layer"].get("compile_s")
