"""The ONE platform gate every kernel and dtype policy asks.

The package runs on two platforms: the TPU (kernels compiled by Mosaic,
bf16 operands on the MXU) and the CPU (tests: Pallas in interpret mode,
fp32 operands).  Anything else is an error, not a guess — a device that
is neither would otherwise interpret kernels orders of magnitude slower
with no message.

Callers reach the function through the module (``platform.on_tpu()``)
so that a test which compiles for a *described* TPU while its process
sits on the CPU can steer every gate with one monkeypatch.
"""

import jax


def on_tpu() -> bool:
    """True on a TPU, False on the CPU, RuntimeError on anything else."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return True
    if platform == "cpu":
        return False
    raise RuntimeError(
        f"theanompi_tpu supports the 'tpu' and 'cpu' platforms; "
        f"jax.devices()[0].platform is {platform!r}"
    )
