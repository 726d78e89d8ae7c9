"""Share of the conv kernels' roofline: the least time the chip could
take for the window's conv work (per step, the larger of its conv FLOPs
over peak FLOP/s and its input, weight and output bytes over peak HBM
bytes/s; counts from the configuration's shapes) over the summed device
time of the Pallas conv kernels in the trace, in percent."""


def read(run):
    if run.trace is None or run.peak is None or run.trace.kernel_s <= 0:
        return None
    bound = sum(run.spec.conv_step_bound_s(b, run.peak) for b in run.steps)
    return 100.0 * bound / run.trace.kernel_s
