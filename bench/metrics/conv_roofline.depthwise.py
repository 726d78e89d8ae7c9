"""Share of the roofline of the depthwise convs (one filter per channel:
``groups == C_in == C_out > 1``): their least time over the window's
steps (per conv and step, the larger of its FLOPs over peak FLOP/s and
its input, weight and output bytes over peak HBM bytes/s, as
``conv_roofline.full_res`` bounds a conv; counts from the
configuration's shapes) over the device time of their Pallas kernels,
found in the trace by the name each kernel carries (``progtrace``), in
percent.  Nothing where a depthwise conv runs no kernel of its name."""

import cellspec
import progtrace


def depthwise_convs(spec) -> list:
    return [n for n in spec.convs
            if n.groups > 1 and n.groups == n.cin == n.channels]


def share(run, convs):
    """Percent of the roofline of ``convs``' named Pallas kernels over
    the run's window, or None where one of them has no kernel time."""
    if run.peak is None or not convs:
        return None
    pt = progtrace.for_run(run)
    if pt is None or any(n.name not in pt.kernel_s_by_layer for n in convs):
        return None
    bound_s = cellspec.load_module("metrics",
                                   "conv_roofline.full_res").conv_bound_s
    kernel_s = sum(pt.kernel_s_by_layer[n.name] for n in convs)
    bound = sum(bound_s(n, b, run.spec.dtype, run.peak)
                for b in run.steps for n in convs)
    return 100.0 * bound / kernel_s if kernel_s > 0 else None


def read(run):
    return share(run, depthwise_convs(run.spec))
