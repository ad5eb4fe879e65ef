"""moe_mxu_share.ep (%): the MoE operations per chip of the traced
window (router, routed experts on the rows received, shared expert;
moe_flops.py) over the device's busy time less the exchange's
(``ragged-all-to-all`` ops), as a share of the chip's bf16 peak
(peaks.json).  Charged for the permutes and norms too, so a
conservative share of the grouped expert matmul's roofline.  Rows
received average tokens x top-k a chip: every routed row lands on some
chip, none is dropped."""
import os

import harness
import moe_flops
from peaks import peaks

_dev = harness.load_module(os.path.dirname(os.path.abspath(__file__)), "",
                           "a2av_device_us.ep")


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    cfg = run.config
    tokens = cfg["assumed"]["prompt_tokens_per_chip"]
    rows = tokens * cfg["num_experts_per_tok"]
    flops = (moe_flops.layer_flops(cfg, tokens, rows)
             * cfg["num_hidden_layers"] * run.units)
    compute_s = t.busy_s - _dev.exchange_s(t)
    if compute_s <= 0:
        return None
    return 100.0 * flops / compute_s / peaks(run.device_kind)[
        "bf16_flops_per_s"]
