"""What the algorithm needs, from shapes alone, and the chip's peaks.

The only place in the repository that counts required operations for the
benchmark. Causal attention is charged for the causal half of the square,
the tied head once at the UNPADDED vocabulary, no position table and no
embedding lookup; recomputed operations never count.
"""

import json
from pathlib import Path

_PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device that is not in the
    table is an error, never a default."""
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       f"{_PEAKS.name} with its source ({sorted(table)})")
    return table[device_kind]


def n_blocks(config) -> int:
    """Weights in the blocks' matrix products: 12·L·E²."""
    return 12 * config["n_layer"] * config["n_embd"] ** 2


def n_head(config) -> int:
    """The tied output head, once, unpadded: V·E."""
    return config["vocab_size"] * config["n_embd"]


def train_flops_per_token(config, seq_len: int) -> float:
    """Forward and backward of one trained token in a causal sequence of
    ``seq_len``: 6·(N_blk + N_head) + 6·L·E·S (scores and the weighted sum,
    2·E·S each for the full square, halved by causality, times three for
    forward and backward)."""
    return (6.0 * (n_blocks(config) + n_head(config))
            + 6.0 * config["n_layer"] * config["n_embd"] * seq_len)


def serve_flops(config, prompt_len: int, first: int, last: int) -> float:
    """Required operations to take one request from ``first`` output tokens
    delivered to ``last``, prompt of ``prompt_len``. A token at position t
    (t earlier tokens in its context) needs 2·N_blk + 4·L·E·t, and
    2·N_head more where a logit is needed: the last prompt token and every
    output token but the final one are the inputs that produce an output.
    The prompt is charged with the first output token."""
    L, E = config["n_layer"], config["n_embd"]

    def span(a, b):                      # positions a .. b-1
        n = max(0, b - a)
        return n * 2.0 * n_blocks(config) + 4.0 * L * E * (a + b - 1) * n / 2.0

    total = 2.0 * n_head(config) * max(0, last - first)
    if last > first:
        lo = 0 if first == 0 else prompt_len + first - 1
        total += span(lo, prompt_len + last - 1)
    return total


def flash_causal_call(batch, heads, seq, head_dim, bytes_per_el=2):
    """Operations and HBM bytes of the causal flash attention of one layer,
    forward plus backward (dq and dkv kernels): forward 2 matrix products
    over the causal half, backward 5 (scores again, dv, dp, dq, dk).
    Bytes: q, k, v, o read or written once forward; q, k, v, o, do read and
    dq, dk, dv written backward."""
    half_square = batch * heads * seq * seq * head_dim     # 2·(S²/2)·D each
    flops = (2 + 5) * half_square
    tensor = batch * heads * seq * head_dim * bytes_per_el
    return {"flops": float(flops), "bytes": float((4 + 8) * tensor)}


def roofline_seconds(cost: dict, peak: dict) -> dict:
    """The least time the chip could take, and which bound sets it."""
    t_c = cost["flops"] / peak["bf16_flops_per_s"]
    t_m = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
