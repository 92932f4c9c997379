"""Operations and bytes from shapes: the least the algorithm needs.

Model FLOPs count every product of the forward pass once and the backward
pass twice; recomputation is not counted.  A multiply-add is two
operations.
"""

from __future__ import annotations

import statistics

from benchmarks.reference import weights


def resnet_forward_macs(c: dict, image_size: int) -> int:
    """Multiply-adds of one image's forward pass: every convolution and the
    head (ResNet-50 at 224: about 4.1e9)."""
    def conv(h_out, kh, cin, cout):
        return h_out * h_out * kh * kh * cin * cout

    h = image_size // 2
    macs = conv(h, 7, 3, c["width"])
    h //= 2  # max pool
    cin = c["width"]
    for _key, cin, mid, stride, has_proj in weights.resnet_blocks(c):
        macs += conv(h, 1, cin, mid)
        h //= stride
        macs += conv(h, 3, mid, mid) + conv(h, 1, mid, 4 * mid)
        if has_proj:
            macs += conv(h, 1, cin, 4 * mid)
        cin = 4 * mid
    return macs + cin * c["num_classes"]


def resnet_train_flops(c: dict, image_size: int) -> float:
    """Forward plus backward of one image."""
    return 3.0 * 2.0 * resnet_forward_macs(c, image_size)


def decode_step_bytes(c: dict, *, slots: int, cache_rows: float, param_bytes: int) -> float:
    """Least bytes one batched decode step reads: the blocks, final norm
    and head once, ``slots`` rows of the embedding and position tables, and
    the keys and values written so far (bf16) of the seated sessions."""
    D, H = c["dim"], c["dim"] * c["mlp_ratio"]
    block = 3 * D * D + D * D + 2 * D * H + H + D + 4 * D
    read_params = c["n_layers"] * block + 2 * D + D * c["vocab_size"] + 2 * slots * D
    cache = cache_rows * c["n_layers"] * 2 * D * 2
    return read_params * param_bytes + cache


def mean_cache_rows(records: list, requests: list, t_a: float, t_b: float) -> float:
    """Cache rows held by the sessions seated between ``t_a`` and ``t_b``,
    averaged over that time, from the clients' stamps: a session has written
    prompt + j rows when its j-th token arrives, and fed its prompt at one
    row a step before the first."""
    prompt_len = {r["id"]: len(r["prompt"]) for r in requests}
    gaps = [b - a for r in records for a, b in zip(r["times"], r["times"][1:]) if b > a]
    if not gaps:
        return 0.0
    step = statistics.median(gaps)
    points = [t_a + (t_b - t_a) * (i + 0.5) / 20 for i in range(20)]
    total = 0.0
    for r in records:
        if not r["times"]:
            continue
        p = prompt_len[r["id"]]
        first, last = r["times"][0], r["times"][-1]
        for t in points:
            if first - p * step <= t <= last:
                total += p + (t - first) / step
    return total / len(points)
