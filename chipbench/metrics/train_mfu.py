"""train_mfu: the whole train step's share of the chip's bf16 peak.

FLOPs per token from ``cost/<config>.py`` (published shapes, no
recomputation) times the window's ``train_tokens_per_s``, over the peak
in ``peaks.json``.  Moves ``train_tokens_per_s``."""


def read(rec):
    layer = rec["layer"]
    if "train_tokens_per_s" not in layer:
        return None
    return (100.0 * layer["train_tokens_per_s"]
            * layer["train_flops_per_token"] / rec["peak"]["bf16_flops_per_s"])
