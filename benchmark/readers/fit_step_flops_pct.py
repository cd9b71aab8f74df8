"""Model FLOPs of a step over what the chips could do in the step's
device time: ``benchmark/flops.py`` counts the FLOPs from the symbol's
shapes, the trace gives the time, ``benchmark/peaks.py`` the bf16 peak.
Idle time between steps is not in the denominator: this is the fused
step's own utilization, and the idle share stands beside it."""
from .. import peaks


def read(slice_):
    trace = slice_.get('trace')
    if not trace or not trace['busy_s'] or not slice_.get('steps'):
        return None
    peak = peaks.peaks_for(slice_['device_kind'])['flops_bf16']
    step_s = trace['busy_s'] / slice_['steps']
    return 100.0 * slice_['step_flops'] / (step_s * slice_['chips'] * peak)
