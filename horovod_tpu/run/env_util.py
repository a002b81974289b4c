"""Process hygiene for spawned worker and benchmark processes."""

from __future__ import annotations

import signal
import sys


def install_sigterm_exit() -> None:
    """Convert SIGTERM into ``SystemExit(143)`` so finalizers actually run.

    CPython leaves SIGTERM at the kernel default (immediate termination, no
    ``finally`` blocks, no atexit, no device-client shutdown), so a parent
    watchdog's SIGTERM-before-SIGKILL escalation buys nothing unless the
    child opts in. Benchmark/tool processes call this at startup: a
    merely-slow process killed by its watchdog then tears down the JAX
    client cleanly instead of dying mid-device-operation. Only installs on
    the main thread; no-op elsewhere."""
    try:
        signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    except ValueError:  # not the main thread
        pass
