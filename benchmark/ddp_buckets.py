"""PyTorch DDP's gradient bucketing rule, for a stage's parameter list.

With ``find_unused_parameters=True`` (the configuration's deployment),
DDP hands ``torch.distributed._compute_bucket_assignment_by_size`` the
module's parameters in registration order and the size limits
``[1 MiB, bucket_cap_mb MiB]``, and keeps the buckets for every step.  Walking the parameters in order, a
parameter joins the open bucket of its dtype; once that bucket holds at
least the current limit it is closed, and the limit moves to the next
entry of the list (the last one stays).  What is left open at the end is
closed too.  The buckets are then sorted by their first parameter and
reversed, so the one holding the last layers' gradients, which backward
produces first, is reduced first.

    python benchmark/ddp_buckets.py benchmark/configs/<config>.json

prints the bucket list (elements per bucket, in reduction order) that
the configuration's ``unit.counts`` must hold.
"""
from __future__ import annotations

import json
import sys

FIRST_BUCKET_BYTES = 1 << 20


def layer_params(cfg: dict) -> list:
    """(name, elements) of one decoder layer, in registration order."""
    h = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    inter = cfg["intermediate_size"]
    sizes = {"q_proj": q * h, "k_proj": kv * h, "v_proj": kv * h,
             "o_proj": h * q, "gate_proj": inter * h, "up_proj": inter * h,
             "down_proj": h * inter, "norm": h}
    order = cfg["assumed"]["layer_param_order"]
    return [(name, sizes["norm" if "norm" in name else name])
            for name in order]


def stage_params(cfg: dict) -> list:
    """Every parameter of the stage's layers, in registration order."""
    return [(f"layers.{i}.{name}", n)
            for i in range(cfg["num_hidden_layers"])
            for name, n in layer_params(cfg)]


def bucket_assignment(sizes_bytes: list, limits: list) -> list:
    """Indices of each bucket in reduction order (one dtype)."""
    buckets, open_idx, open_bytes, li = [], [], 0, 0
    for i, nbytes in enumerate(sizes_bytes):
        open_idx.append(i)
        open_bytes += nbytes
        if open_bytes >= limits[li]:
            buckets.append(open_idx)
            open_idx, open_bytes = [], 0
            li = min(li + 1, len(limits) - 1)
    if open_idx:
        buckets.append(open_idx)
    buckets.sort(key=min)
    return buckets[::-1]


def bucket_counts(cfg: dict) -> list:
    """Elements per bucket, in the order DDP reduces them."""
    params = stage_params(cfg)
    itemsize = {"float32": 4, "bfloat16": 2}[cfg["unit"]["dtype"]]
    cap = cfg["deployment"]["bucket_cap_mb"] << 20
    buckets = bucket_assignment([n * itemsize for _, n in params],
                                [FIRST_BUCKET_BYTES, cap])
    return [sum(params[i][1] for i in b) for b in buckets]


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        print(json.dumps(bucket_counts(json.load(f))))
