"""Which TPU chips this host offers, found without taking them.

A chip belongs to one process at a time, and initialising a JAX backend
takes every chip the process can see. Code that only hands chips out —
the ``fleet`` parent, a test deciding whether to skip — must therefore
count them some other way: by the device files the TPU driver exposes,
which is also what libtpu enumerates at start-up. (The PCI bus is no
guide: a container may be handed one chip of a four-chip host.)
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence


def local_tpu_chips() -> List[int]:
    """Indices of the TPU chips a process started from here may use:
    the ones ``TPU_VISIBLE_CHIPS`` names when it is set, else one per
    device file (``/dev/vfio/<n>`` on v5e and later, ``/dev/accel<n>``
    before). Empty on a host without a TPU."""
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        return [int(c) for c in visible.split(",") if c.strip()]
    files = glob.glob("/dev/accel[0-9]*") or [
        f for f in glob.glob("/dev/vfio/*")
        if os.path.basename(f).isdigit()]
    return list(range(len(files)))


def chips_env(chips: Sequence[int]) -> Dict[str, str]:
    """Environment that makes a child process see exactly ``chips`` as
    its whole one-process topology; libtpu reads these at backend
    start-up. Verified for ONE chip on the v5e 2x2 host (four children,
    one chip each, at once, three times); the two-chip form came up in
    one of two tries there and nothing relies on it."""
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": f"{len(chips)},1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
