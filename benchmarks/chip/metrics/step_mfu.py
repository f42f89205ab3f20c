"""The whole step's share of the chips' peak in the traced window: the
model's FLOPs in the traced steps over chips x bf16 peak x the window.
It bounds every kernel's roofline claim from above, and counts the idle
time that a kernel's share leaves out."""


def read(r, facts):
    return 100.0 * facts["flops_per_step"] * r.steps / (
        r.chips * facts["peak_flops_per_s"] * r.window_s)
