"""Model FLOPs of one training step over the device time of one step in
the trace times the chip's bf16 peak, in percent: the median execution
of the step program on the device.  Recomputation is not counted."""
import statistics

from harness import flops


def read(run):
    if run.trace is None:
        return None
    times = run.trace.module_times("jit_step")
    shapes = {(run.jobs[s.job].batch, run.jobs[s.job].seq)
              for s in run.segments if s.steps}
    if not times or len(shapes) != 1:
        return None
    (batch, seq), = shapes
    work = flops.per_token(run.cell.config, seq) * batch * seq
    return 100.0 * work / (statistics.median(times)
                            * flops.peaks(run.device_kind)["bf16_flops_per_s"])
