"""The look for a chip that every entry point makes before it runs."""
from __future__ import annotations

import sys


def devices_or_exit(chips: int, what: str):
    """JAX's devices when they are ``chips`` TPU chips or more; otherwise
    say why on standard error and exit 2, printing no result."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no accelerator: {e}", file=sys.stderr)
        sys.exit(2)
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {what} needs {chips} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)
    from repro.kernels.backend import use_compile_cache

    use_compile_cache()
    return devices
