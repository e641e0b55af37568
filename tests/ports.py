"""Ports for tests that start `kfrun`.

kfrun's defaults (`-port-range 38000-38999`, `-runner-port 38080`,
`-monitor-port 7756`) are one block for the whole machine, so two tests
that start it at once collide ("Address already in use"), and the driver
runs the tests with six pytest-xdist workers. `kfrun_ports()` gives each
xdist worker a block of its own: 1000 ports from 22000 up, below the
kernel's ephemeral range (32768 and up here, which holds kfrun's default
38000 block) and clear of test_transport's 21001-21999. Tests of one
worker run one after another, so they share a block as they shared the
default one.
"""

from __future__ import annotations

import os
import re
from typing import List, NamedTuple

FIRST_BLOCK = 22000
BLOCK = 1000
BLOCKS = 10  # 22000..31999


class KfrunPorts(NamedTuple):
    base: int  # workers take base, base + 1, ... in rank order on one host

    @property
    def args(self) -> List[str]:
        """The arguments that move every port kfrun opens by default."""
        return ["-port-range", f"{self.base}-{self.base + 899}",
                "-runner-port", str(self.runner),
                "-monitor-port", str(self.monitor)]

    @property
    def runner(self) -> int:
        return self.base + 900

    @property
    def monitor(self) -> int:
        return self.base + 910

    def spare(self, i: int = 0) -> int:
        """A port of the block kfrun takes for nothing by itself: for a
        test's `-debug-port`."""
        return self.base + 950 + i

    def worker(self, rank: int, host: str = "127.0.0.1") -> str:
        """`host:port` of a worker, as peer lists and KF_SHAPE_LINKS name it."""
        return f"{host}:{self.base + rank}"


def kfrun_ports() -> KfrunPorts:
    """The block of this pytest-xdist worker (`gw3` -> the fourth), or the
    first block without xdist."""
    digits = re.sub(r"\D", "", os.environ.get("PYTEST_XDIST_WORKER", ""))
    return KfrunPorts(FIRST_BLOCK + BLOCK * (int(digits or 0) % BLOCKS))
