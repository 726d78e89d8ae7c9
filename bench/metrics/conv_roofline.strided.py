"""Share of the roofline of the strided convs (``stride > 1``; ResNet-50:
the 7x7/s2 stem, the three 3x3/s2 convs and the three 1x1/s2
projections), read as ``conv_roofline.depthwise`` reads the depthwise
ones: their per-conv, per-step roofline bound (counts from the
configuration's shapes) over the device time of the Pallas kernels that
carry their names, in percent.  Nothing where a strided conv runs no
kernel of its name."""

import cellspec


def strided_convs(spec) -> list:
    return [n for n in spec.convs if n.stride > 1]


def read(run):
    return cellspec.load_module("metrics", "conv_roofline.depthwise").share(
        run, strided_convs(run.spec))
