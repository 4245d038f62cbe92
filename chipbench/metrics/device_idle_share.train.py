"""device_idle_share.train: 1 - busy / window over a few steady train
steps traced after the window (busy is the union of the device's
operations, see ``trace.py``).  Moves ``train_tokens_per_s``."""


def read(rec):
    t = rec.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
