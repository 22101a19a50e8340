"""What a per-layer reader is given."""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class Context:
    trace: Optional[object]   # chipbench.trace.Summary of the window
    counters: dict            # ServeStats deltas over the window, and
    #                           "lifecycle": LifecycleStats at its end
    window: dict              # batches applied/dispatched in the window
    shapes: dict              # kernel call shapes the harness observed
    peaks: dict               # chipbench.peaks entry of the device
