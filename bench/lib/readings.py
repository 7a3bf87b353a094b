"""What a metric's reader is handed, and the arithmetic the roofline and
operand readers share. A reader returns None when its cell gives it
nothing to read; the harness then leaves the metric out."""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

from bench.lib import counts, systems, trace

# kernels that stream the selection's matrix once per greedy step, and the
# operand each streams
STREAMED = {"greedy_loop_pallas": 0, "gains_pallas": 2}


@dataclasses.dataclass
class Readings:
    window: systems.Window              # the window's answers and clock
    selections: int                     # completed in the window
    setup_s: float                      # process start to window start
    peak_bytes: int                     # device memory peak, fullest chip
    lowerings: int                      # lowerings inside the window
    summary: Optional[trace.Summary] = None   # the traced window, reduced
    inventory: List[counts.Kernel] = dataclasses.field(default_factory=list)
    peaks: object = None                # peaks.Peaks of this device
    events: List[List[dict]] = dataclasses.field(default_factory=list)
    logical: Tuple[int, int] = (0, 0)   # (ground rows, candidates), leaf


def roofline_share(r: Readings, names: Sequence[str]) -> Optional[float]:
    """Percent of the kernels' device time that their counted operations
    and bytes need at the chip's peaks. None when none of them ran, or
    when one that ran cannot be counted."""
    if r.summary is None:
        return None
    ideal = spent = 0.0
    for name in names:
        for op in r.summary.kernel(name):
            shapes = tuple(d for _, d in trace.result_shapes(op.name))
            k = counts.match(r.inventory, name, shapes)
            least = k.least_seconds(r.peaks) if k is not None else None
            if least is None:
                return None
            ideal += least
            spent += (op.end - op.start) * 1e-9
    return 100.0 * ideal / spent if spent > 0 else None


def streamed(r: Readings) -> Optional[counts.Block]:
    """The largest matrix a selection kernel re-reads every step."""
    blocks = [k.inputs[STREAMED[k.name]] for k in r.inventory
              if k.name in STREAMED]
    return max(blocks, key=lambda b: b.nbytes) if blocks else None


def pad_share(r: Readings) -> Optional[float]:
    b = streamed(r)
    if b is None:
        return None
    return 100.0 * (1.0 - math.prod(r.logical) / math.prod(b.shape))
