"""Share of the roofline of the pointwise (1x1) convs, read as
``conv_roofline.depthwise`` reads the depthwise ones: their per-conv,
per-step roofline bound over the device time of the Pallas kernels that
carry their names, in percent.  Nothing where a 1x1 conv runs no
kernel of its name."""

import cellspec


def read(run):
    convs = [n for n in run.spec.convs if n.k == 1]
    return cellspec.load_module("metrics",
                                "conv_roofline.depthwise").share(run, convs)
