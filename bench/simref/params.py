"""Requester classes and the configuration record the benchmark's traffic
and metric code read: the `sim_config` fields of a configuration file, as
attributes."""
from __future__ import annotations

from typing import Any, Tuple

CLS_CPU, CLS_GPU, CLS_HWA = 0, 1, 2
CLASS_NAMES: Tuple[str, ...] = ("cpu", "gpu", "hwa")


class SimConfig:
    """A configuration file's `sim_config` fields as attributes."""

    def __init__(self, **fields: Any):
        self.__dict__.update(fields)

    @property
    def n_src(self) -> int:
        return self.n_cpu + self.n_gpu + self.n_hwa
