"""The six rtlint passes, in catalog order (docs/INVARIANTS.md)."""

from tools.rtlint.passes.wire import WirePass
from tools.rtlint.passes.knobs import KnobsPass
from tools.rtlint.passes.locks import LocksPass
from tools.rtlint.passes.clocks import ClocksPass
from tools.rtlint.passes.metrics import MetricsPass
from tools.rtlint.passes.framebudget import FrameBudgetPass

ALL_PASSES = (WirePass, KnobsPass, LocksPass, ClocksPass, MetricsPass,
              FrameBudgetPass)

__all__ = ["ALL_PASSES", "WirePass", "KnobsPass", "LocksPass",
           "ClocksPass", "MetricsPass", "FrameBudgetPass"]
