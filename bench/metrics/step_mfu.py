"""The whole step's share of the chip's peak: conv FLOPs per image (the
benchmark's own count from the configuration's shapes) times images per
second over the window, over the peak FLOP/s of the configuration's
dtype (``bench/peaks.json``), in percent."""


def read(run):
    if run.peak is None or run.completed == 0:
        return None
    rate = run.completed / run.window_s
    return 100.0 * run.spec.conv_flops_per_image * rate / run.peak["flops"]
