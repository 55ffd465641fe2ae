"""What the held experts' grouped products in a decode step need, from
shapes alone and the program's own counts (``configs/*.json`` with routed
experts; ``flops.py`` holds the chip's peaks).

An expert is of the kind its configuration names: SwiGLU (``silu``: gate,
up and down, three matrices of ``hidden x moe_intermediate_size``) or
squared ReLU (``relu2``: up and down, two). A decode step's products read
the weights of every held expert that took at least one token-expert pair
once (``experts_touched`` of the program's spans), and the pairs' rows:
each pair's input row (``hidden``, bfloat16) read, its intermediate rows
(the gate and up product's ``moe_intermediate_size``, bfloat16) written
by the first product and read by the second, its output row (``hidden``,
float32) written. Two operations a weight a pair.
"""

BF16, F32 = 2, 4


def expert_matrices(c) -> int:
    """Matrices of ``hidden x moe_intermediate_size`` in one expert."""
    kind = c.get("mlp_hidden_act", c.get("hidden_act"))
    return {"silu": 3, "relu2": 2}[kind]


def expert_decode_cost(c, touched: int, pairs: int) -> dict:
    """Operations and HBM bytes of the held experts' grouped products over
    ``touched`` expert-layers with a pair and ``pairs`` token-expert pairs
    held here, summed over a decode step's expert layers by the caller."""
    E, M = c["hidden_size"], c["moe_intermediate_size"]
    weights = expert_matrices(c) * E * M
    intermediate = (expert_matrices(c) - 1) * M
    return {"flops": float(2 * pairs * weights),
            "bytes": float(touched * weights * BF16 + pairs * (
                E * BF16 + 2 * intermediate * BF16 + E * F32))}
