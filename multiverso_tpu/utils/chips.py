"""One process per chip: how a launcher hands each child its own TPU chip.

A TPU chip belongs to one process at a time. A launcher (``spawn_ranks``,
the fleet ``local``/``ps_fleet`` roles, ``scripts/serve_bench.py``) therefore
never initializes a jax backend itself, and gives every child that will hold
a device exactly one chip through the child's environment before it starts.
Nothing here imports jax.

What libtpu 0.0.34 honors on a four-chip v5e host (measured, PR 21):
``TPU_VISIBLE_CHIPS=<i>`` together with ``TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1``
and ``TPU_PROCESS_BOUNDS=1,1,1`` lets four processes run at once, each seeing
one device. ``TPU_VISIBLE_CHIPS`` alone fails on libtpu's multi-process
lockfile. A parent that has taken chip 0 this way can still start a child on
chip 1.

The platform itself is chosen by the environment and nowhere else: with
``JAX_PLATFORMS=cpu`` (tests, CPU drives) no child holds a chip and every
environment is left alone.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import signal
import threading
from typing import Dict, Optional

from multiverso_tpu.utils.log import FatalError


def host_chips() -> int:
    """TPU chips on this host, counted from the device nodes the kernel
    exposes (``/dev/vfio/<n>`` on v5e and later, ``/dev/accel<n>`` before) —
    counting through jax would initialize a backend and take them. 0 when
    ``JAX_PLATFORMS`` names another platform first, or on a host without
    chips: processes there do not compete for a device."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and platforms.split(",")[0].strip() != "tpu":
        return 0
    nodes = [p for p in glob.glob("/dev/vfio/*") + glob.glob("/dev/accel*")
             if re.search(r"\d+$", p)]
    return len(nodes)


def chip_env(chip: int) -> Dict[str, str]:
    """Environment that restricts a process to chip ``chip`` of this host."""
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def child_env(slot: int, holders: int, what: str) -> Optional[Dict[str, str]]:
    """The full environment for chip-holding process number ``slot`` of
    ``holders`` (children, plus the launcher itself where it keeps a seat),
    or None to inherit this one unchanged (no chips to share out, or one
    process on a one-chip host). More holders than chips is an error — never
    a quiet move to the CPU."""
    chips = host_chips()
    if chips == 0:
        return None
    if holders > chips or slot >= chips:
        raise FatalError(
            f"{what}: {max(holders, slot + 1)} processes each need a TPU "
            f"chip but this host has {chips}; start fewer, or set "
            "JAX_PLATFORMS=cpu to run them off the chip")
    if chips == 1:
        return None
    return dict(os.environ, **chip_env(slot))


def take_chip(slot: int, holders: int, what: str) -> None:
    """Restrict THIS process to chip ``slot`` — for a launcher that keeps a
    seat of its own (the ps_fleet parent holds rank 0). Call before the
    first jax device use."""
    env = child_env(slot, holders, what)
    if env is not None:
        os.environ.update(chip_env(slot))


def _interrupt(signum, frame):
    raise KeyboardInterrupt


@contextlib.contextmanager
def sigterm_as_interrupt():
    """For a launcher's main thread: SIGTERM takes the KeyboardInterrupt
    path, so the ``finally`` blocks that stop its children run. The default
    action kills the launcher where it stands and orphans them, and an
    orphan keeps its chip: on the four-chip host the next launcher's
    children then die on ``open(/dev/vfio/1): Device or resource busy``
    (PR 21)."""
    if threading.current_thread() is not threading.main_thread():
        yield                       # handlers can only be set there
        return
    previous = signal.signal(signal.SIGTERM, _interrupt)
    try:
        yield
    finally:
        signal.signal(signal.SIGTERM, previous)
