"""Each cost function against a count made by hand at a tiny shape."""

from chipbench import harness

M = {"num_layers": 2, "d_model": 8, "vocab_size": 10, "tie_embeddings": False,
     "ssm": {"state_dim": 4, "head_dim": 4, "expand": 2, "conv_width": 4,
             "chunk_size": 4, "ngroups": 1}}


def test_mamba2_forward_and_train_flops_by_hand():
    cost = harness.load_module(harness.HERE / "cost" / "mamba2-370m.py")
    # d 8, di 16, heads 4 of 4 channels, N 4, one group, chunk 4
    proj = 2 * 8 * (2 * 16 + 2 * 4 + 4) + 2 * 16 * 8          # 960
    conv = 2 * 4 * (16 + 8)                                     # 192
    ssd = 4 * 5 + 4 * 4 * 5 + 2 * 64 + 2 * 64 + 2 * 64 / 4     # 388
    head = 2 * 8 * 10                                           # 160
    want = 2 * (proj + conv + ssd) + head                       # 3240
    assert cost.forward_flops_per_token(M, 16) == want == 3240
    assert cost.train_flops_per_token(M, 16) == 3 * want
    # a sequence shorter than a chunk sees (seq + 1) / 2 positions
    short = 2 * (proj + conv + 4 * 3 + 16 * 3 + 2 * 64 + 2 * 64 + 2 * 64 / 2) \
        + head
    assert cost.forward_flops_per_token(M, 2) == short
