"""ckpt_read_gb: bytes the DFS served in one restart (``HdfsCluster``'s
read accounting, startup DAG and training loop together, deferred waves
drained), in GB; mean over the window's restarts.  Moves ``restart_s``."""


def read(rec):
    return rec["layer"].get("ckpt_read_gb")
