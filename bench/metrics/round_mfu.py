"""The compiled round's share of the chip's peak (percent): model FLOPs of
the samples the window's cohorts actually trained (each selected client's
size times its local epochs, times the model's FLOPs per sample, from
``bench/roofline.py``) over the window's seconds and the chip's bf16 peak.
Padded steps and the eval pass do not count."""


def read(run):
    if run.peak is None or run.window_s <= 0 or run.model_flops_window <= 0:
        return None
    return 100.0 * run.model_flops_window / run.window_s / float(run.peak["bf16_flops_per_s"])
