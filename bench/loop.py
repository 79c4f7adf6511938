"""What every window driver shares, and how a mix finds its driver.

A traffic mix names its driver (``"loop"``): ``bench/loops/<name>.py``,
found by name, whose ``LOOP`` class builds what the mix drives, warms up
every shape the window will use (set-up), runs the measured window, and
afterwards checks what the timed path produced against the plain
references, each number against its limit in the mix's ``"limits"``.
Around each call into the program a driver opens a
``jax.profiler.TraceAnnotation`` named ``bench.<what>``, so that a traced
run can tell what the host was doing while the device sat idle.
"""
from __future__ import annotations

import dataclasses
import importlib
import time

import jax

clock = time.perf_counter


def note(name: str):
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def find(name: str) -> type:
    """The driver class of ``bench/loops/<name>.py``."""
    return importlib.import_module(f"{__package__}.loops.{name}").LOOP


@dataclasses.dataclass
class Check:
    """One number compared with its limit; the run is correct iff every
    value is at most its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


class Loop:
    """Common shape: ``setup`` -> ``window`` -> ``free`` -> ``checks``;
    ``plant_control`` puts the control in the program's place."""

    #: the driver runs on the configuration's DFEP owner array
    needs_owner = True

    def __init__(self, dep, mix: dict, seed: int, seconds: float):
        self.dep, self.mix, self.seed = dep, mix, int(seed)
        self.seconds = float(seconds)
        self.limits = mix["limits"]
        self.window_s = None
        self.attempted = 0
        self.failed = 0

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""

    def notes(self) -> dict:
        """What the run shows beside its metrics (not compared)."""
        return {}

    def check(self, name: str, value: float) -> Check:
        return Check(name, float(value), float(self.limits[name]))
