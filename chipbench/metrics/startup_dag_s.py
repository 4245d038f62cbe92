"""startup_dag_s: the BootSeer startup DAG's own span
(``StartupResult.total_s``), mean over the window's restarts.  Moves
``restart_s``."""


def read(rec):
    return rec["layer"].get("startup_dag_s")
