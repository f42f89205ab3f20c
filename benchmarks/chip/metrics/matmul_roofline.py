"""The matrix products' share of their roofline: the model's FLOPs in the
traced steps (flops/<family>.py, no recomputation, all bound by compute)
over the bf16 peak times the device time of every op that holds a dot or
a convolution, summed over the devices.  Time that a recomputed forward
spends in dots counts in the denominator only, so full rematerialisation
reads below 100% even at the peak."""


def read(r, facts):
    dot_s = sum(c["dot"] for c in r.class_s)
    if dot_s <= 0:
        return None
    return 100.0 * facts["flops_per_step"] * r.steps / (
        facts["peak_flops_per_s"] * dot_s)
