"""Host-platform environment helpers (jax-free at import time).

One home for the XLA virtual-device-count dance so its rule lives in
one place (tests/conftest.py keeps a private inline copy because its
bootstrap must run before this package can be imported), and for the
one rule that decides whether Pallas kernels run in interpret mode.
"""
from __future__ import annotations

import os

_FLAG = "xla_force_host_platform_device_count"


def ensure_host_device_count(n: int) -> None:
    """Make the CPU platform expose at least `n` virtual devices by
    appending ``--xla_force_host_platform_device_count=n`` to
    ``XLA_FLAGS`` — a no-op if the flag is already set (the caller's
    explicit choice wins).  Must run BEFORE the first jax import; to
    actually select the CPU platform also call
    ``jax.config.update("jax_platforms", "cpu")`` after importing jax
    (environment hooks may pin a hardware platform at interpreter
    start; see docs/troubleshooting.md)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} --{_FLAG}={n}".strip()


def pallas_interpret() -> bool:
    """True on the CPU platform, where Pallas kernels run under the TPU
    interpreter; False on the TPU, where they compile.  Any other
    platform has no lowering here and raises."""
    import jax

    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas lowering for platform {backend!r} "
                       "(supported: tpu, and cpu in interpret mode)")
