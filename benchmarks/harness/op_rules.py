"""Per-layer readings of the cells that no older reader knows: device
milliseconds a whole step of the ops that a rule on their own HLO line
picks, and a gauge of the program's registry.

A profile names an op's events by its HLO line (result and operands with
their shapes), so a rule on the line finds an op by the arrays it makes
and reads, whatever fusion XLA put it in. Only whole step programs count:
one the profile's edge cut holds fewer such ops than the others and is
left out (``named_kernels.kernel_seconds`` does the same for kernels).
"""

import functools
import math
import re
from typing import Callable, Optional

from benchmarks.harness import trace_reduce

PALLAS = 'custom_call_target="tpu_custom_call"'
CONTROL_FLOW = (" while(", " conditional(", " call(")
ARRAY = re.compile(r"\w+\[([\d,]+)\]")


def dims_in_line(name: str):
    """Every dimension of every array the HLO line names."""
    return {int(d) for dims in ARRAY.findall(name)
            for d in dims.split(",") if d}


def plain_op(name: str) -> bool:
    """Neither a Pallas call nor control flow that holds other ops."""
    return PALLAS not in name and not any(w in name for w in CONTROL_FLOW)


def step_ms(ctx, picks: Callable[[str], bool]) -> Optional[float]:
    """Mean device milliseconds a whole step program on chip 0 of the ops
    that ``picks(hlo_line)`` takes, each op at its self time; None
    without a trace or such an op."""
    if not ctx.get("trace_raw"):
        return None
    planes = trace_reduce.device_planes(ctx["trace_raw"])
    if not planes:
        return None
    picks = functools.lru_cache(maxsize=None)(picks)  # a name recurs
    ops = trace_reduce.line_events(planes[0], trace_reduce.OPS_LINE)
    by_step = [[e for e in ops if s[1] <= e[1] and e[1] + e[2] <= s[1] + s[2]]
               for s in trace_reduce.step_events(planes[0],
                                                 ctx["step_module"])]
    counts = [sum(picks(e[0]) for e in step) for step in by_step]
    most = max(counts, default=0)
    if not most:
        return None
    whole = [step for step, n in zip(by_step, counts) if n == most]
    return sum(sum(own for name, own in trace_reduce.self_times(step).items()
                   if picks(name)) for step in whole) / 1e6 / len(whole)


def registry_value(ctx, name: str) -> Optional[float]:
    """The unlabelled sample ``name`` of the program's registry when the
    run ends (a job that runs the program in child processes hands the
    worker's rendered registry back as ``ctx["registry_text"]``); None
    without a job or such a sample (a program that has no such gauge),
    or where it reads NaN (nothing to compute it from)."""
    if not ctx.get("job"):
        return None
    text = ctx.get("registry_text")
    if text is None:
        from dlrover_tpu.observability.registry import get_registry

        text = get_registry().render()
    for line in text.splitlines():
        if line.startswith(name + " "):
            value = float(line.split()[1])
            return None if math.isnan(value) else value
    return None
