"""Operations of one DeepSeek-V3 MoE layer on one chip, from its shapes
(the yardstick of ``moe_mxu_share.ep``): the router over every expert,
the routed experts on the rows the chip received, the shared expert,
each a multiply-add counted as two operations.  Softmax-free gating,
norms and permutes are not counted."""
from __future__ import annotations


def router_flops(cfg: dict, tokens: int) -> float:
    return 2.0 * tokens * cfg["hidden_size"] * cfg["n_routed_experts"]


def routed_flops(cfg: dict, rows: float) -> float:
    """SwiGLU experts (three projections) on `rows` (token, expert)
    rows."""
    return 6.0 * rows * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_flops(cfg: dict, tokens: int) -> float:
    width = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return 6.0 * tokens * cfg["hidden_size"] * width


def layer_flops(cfg: dict, tokens: int, rows: float) -> float:
    """One MoE layer of `tokens` tokens on a chip that received `rows`
    rows for its experts."""
    return (router_flops(cfg, tokens) + routed_flops(cfg, rows)
            + shared_flops(cfg, tokens))
