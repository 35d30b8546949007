"""Test harness config.

Tests run hermetically on CPU with 8 virtual XLA devices so multi-chip sharding
logic is exercised without TPU hardware.  ``JAX_PLATFORMS=cpu`` (the tier-1
command sets it) selects the CPU backend with one device; force_cpu(8) also
sets the virtual-device count, before any backend is created.  What only a
chip can show is in ``chip_smoke.py`` (run through the chip tool).
"""

import os

os.environ.setdefault("JAX_ENABLE_X64", "0")

from tpulab.tpu.platform import force_cpu  # noqa: E402

force_cpu(8)
# The persistent XLA compilation cache is not enabled here: in-process
# compile reuse for the serving engine comes from the step programs' memo
# (engine/paged_steps.py _JIT_MEMO), which shares jitted programs across
# identical-geometry engines without any serialization.


def free_port() -> int:
    """Ephemeral localhost port (best-effort: tiny close-to-rebind window)."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
