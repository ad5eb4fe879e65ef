"""experts_hbm_share.scmoe (%): the bytes the grouped expert matmuls must
move per chip in the traced window (the held experts' weights read once
a layer, the received rows in and out; moe_bytes.py) over the device
time of those kernels (profiler trace), as a share of the chip's HBM
bandwidth (peaks.json).  The rows received a layer are the layers'
device tally, reported by the generator as each alltoallv call's mean
payload.  The kernels are the expert program's Pallas custom calls, the
``%_experts`` ops.  None where the trace holds none."""
import moe_bytes
from peaks import peaks


def kernel_s(trace) -> float:
    """Seconds of the grouped-matmul kernels per chip in `trace`."""
    return sum(s for name, s in trace.ops
               if name.startswith("%_experts") and name.endswith(
                   " custom-call"))


def read(run):
    t = run.trace
    if t is None:
        return None
    s = kernel_s(t)
    if s <= 0:
        return None
    cfg = run.config
    hidden = cfg["hidden_size"]
    payload = [nbytes for op, nbytes in run.calls if op == "alltoallv"]
    layers = len(payload) // 2   # dispatch and combine a layer
    if not layers:
        return None
    rows = payload[0] / (hidden * moe_bytes.BF16)
    held = len(cfg["deployment"]["held_experts"])
    nbytes = moe_bytes.layer_bytes(hidden, cfg["expert_ffn_hidden_size"],
                                   held, rows) * layers * run.units
    return 100.0 * nbytes / s / peaks(run.device_kind)["hbm_bytes_per_s"]
