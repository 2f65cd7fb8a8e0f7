"""ivf_adc_roofline: the ADC scan kernel's share of its roofline, in %.

The least time the scan could take on the chip (distinct live rows of the
probed lists with their ids, the LUTs and the top-k out, over peak HBM
bandwidth, or the additions over peak FLOP/s, whichever is larger;
``bench/work.py scan``) over the summed device time of the ``ivf_adc``
kernel in the traced window.
"""

#: how the kernel shows in the trace: the Pallas call is named after its
#: kernel function (kernels/ivf_adc.py)
NAMES = ("ivf_adc",)


def read(run, reduced):
    v = run.values
    if reduced is None or not v.get("scan_roofline_s"):
        return None
    kernel_s = reduced.seconds_matching(*NAMES)
    if not kernel_s:
        return None
    return 100.0 * v["scan_roofline_s"] / kernel_s
