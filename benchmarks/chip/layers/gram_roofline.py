"""kernels layer (kernels/megabatch.py): the Gram kernels' share of
their roofline, in percent.  The least time the chip could take for the
Gram work of the fits launched in the traced window (chipbench/
roofline.py: least work at real shapes against the published peak of
the device kind) over the summed device time of the kernels registered
under kernels/gram/."""

from chipbench import roofline, trace


def read(w):
    if w.trace is None:
        return None
    kernel_s = trace.kernel_ns(w.trace, w.kernel_names("gram")) / 1e9
    fits = w.counters["tasks"]
    if kernel_s <= 0 or fits <= 0:
        return None
    least = roofline.least_gram_s(fits, w.cell.config, w.device_kind)
    return 100.0 * least / kernel_s
