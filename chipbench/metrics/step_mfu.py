"""Model FLOPs that the window's batches require (counted by the
benchmark's model file from its own replay), over the window's seconds
times the chip's published bf16 peak, in percent."""


def read(rec):
    if rec.flops is None or rec.peaks is None or not rec.flops_window_s:
        return None
    return 100.0 * rec.flops / (rec.flops_window_s * rec.peaks["bf16_flops_per_s"])
