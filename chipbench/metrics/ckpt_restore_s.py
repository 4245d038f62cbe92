"""ckpt_restore_s: seconds the training loop spent in the checkpointer's
``restore_planned`` and waiting on its async optimizer tail, timed by the
benchmark's proxy of the checkpointer; mean over the window's restarts.
Moves ``restart_s``."""


def read(rec):
    return rec["layer"].get("ckpt_restore_s")
