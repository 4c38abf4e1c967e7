"""The one gate every device entry point passes before its first kernel.

The hot path of this system is JAX on a TPU.  JAX itself is lenient: with
``JAX_PLATFORMS`` unset and no usable chip (none attached, or another
process holds it) it initialises the CPU backend and says nothing, so a
"TPU run" can execute its kernels on XLA:CPU.  :func:`require_device`
turns that into an error: after backend init the first device must be a
TPU, unless the CPU was asked for by name (``JAX_PLATFORMS=cpu`` or
``DSI_JAX_PLATFORM=cpu``, as the tests and the verify recipe do).

The same call places the compile cache (``utils/compilecache.py``), so an
entry point has one line to get right.
"""

from __future__ import annotations

import os
import sys

# sysexits' EX_UNAVAILABLE.  A code of its own, so that a launcher can tell
# "this worker could claim no chip" (respawning meets the same chip) from a
# crash (respawn): cli/chips.lost_chip.
NO_ACCELERATOR_EXIT = 69


class NoAcceleratorError(SystemExit):
    """No TPU and the CPU was not asked for by name.  A ``SystemExit``
    whose code is ``NO_ACCELERATOR_EXIT``: an entry point that does not
    catch it exits with that code — never a traceback, never a result.
    :func:`require_device` writes the message to stderr as it raises."""

    def __init__(self, message: str):
        super().__init__(message)       # str(e) is the message
        self.code = NO_ACCELERATOR_EXIT


def _refuse(message: str) -> NoAcceleratorError:
    print(message, file=sys.stderr, flush=True)
    return NoAcceleratorError(message)


def requested_platform(env=None) -> str:
    """The platform the environment names (``DSI_JAX_PLATFORM`` wins over
    ``JAX_PLATFORMS``), or ``""`` when it names none."""
    env = os.environ if env is None else env
    return (env.get("DSI_JAX_PLATFORM") or env.get("JAX_PLATFORMS")
            or "").strip().lower()


def cpu_requested(env=None) -> bool:
    return requested_platform(env) == "cpu"


def require_device(who: str = "dsi_tpu"):
    """Initialise the JAX backend and return ``jax.devices()``, or raise
    :class:`NoAcceleratorError` when the platform is neither a TPU nor an
    explicitly requested CPU.  ``who`` prefixes the message."""
    plat = requested_platform()
    import jax

    if plat and plat != (os.environ.get("JAX_PLATFORMS") or "").lower():
        jax.config.update("jax_platforms", plat)  # DSI_JAX_PLATFORM

    from dsi_tpu.utils.compilecache import place_compile_cache

    place_compile_cache()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise _refuse(
            f"{who}: no TPU: JAX could not initialise a backend "
            f"({str(e).splitlines()[0][:200]}). One process may hold a "
            "chip at a time; set JAX_PLATFORMS=cpu to run the kernels "
            "on the CPU on purpose.") from None
    got = devices[0].platform
    if got != "tpu" and not (got == "cpu" and plat == "cpu"):
        raise _refuse(
            f"{who}: no TPU: JAX initialised platform {got!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}). "
            "The chip is missing or held by another process; set "
            "JAX_PLATFORMS=cpu to run the kernels on the CPU on purpose.")
    return devices
