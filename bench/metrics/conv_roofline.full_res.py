"""Share of the roofline of the convs that run at the input's full
spatial size (VGG-16: conv1, conv2; U-Net: enc0a, enc0b, dec0r, dec0a,
dec0b, out): their least time over the window's steps (per conv and
step, the larger of its FLOPs over peak FLOP/s and its input, weight and
output bytes over peak HBM bytes/s; counts from the configuration's
shapes) over the device time of their Pallas kernels, found in the
trace by the name each kernel carries (``progtrace``), in percent.
Nothing where the program names no kernel after its conv."""

import cellspec
import progtrace


def conv_bound_s(node, batch: int, dtype: str, peak: dict) -> float:
    """Roofline bound of one conv over a batch: FLOPs over peak, or its
    input, weights and bias, and output bytes over HBM peak."""
    b = cellspec.DTYPE_BYTES[dtype]
    moved = b * (batch * (node.size_in ** 2 * node.cin
                          + node.size ** 2 * node.channels)
                 + node.n_params)
    return max(batch * node.flops / peak["flops"],
               moved / peak["hbm_bytes_per_s"])


def full_res_convs(spec) -> list:
    return [n for n in spec.convs if n.size == spec.image]


def read(run):
    if run.peak is None:
        return None
    pt = progtrace.for_run(run)
    if pt is None:
        return None
    convs = full_res_convs(run.spec)
    if not convs or any(n.name not in pt.kernel_s_by_layer for n in convs):
        return None
    kernel_s = sum(pt.kernel_s_by_layer[n.name] for n in convs)
    bound = sum(conv_bound_s(n, b, run.spec.dtype, run.peak)
                for b in run.steps for n in convs)
    return 100.0 * bound / kernel_s if kernel_s > 0 else None
