"""body.prefill_mfu: the model FLOPs of every prefill step that ran in the
traced slice (the module's ``flops``, from shapes) over their summed device
time times the chip's bf16 peak (``peaks.py``), in percent: prefill's share
of the compute roofline.  Device trace.  Moves ``p50_ms``."""


def read(rec):
    if rec.peaks is None:
        return None
    lo, hi = rec.window
    flops = seconds = 0.0
    for name, s, e in rec.trace.modules:
        step = rec.modules.get(name)
        if step and step["kind"] == "prefill" and lo <= s and e <= hi:
            flops += step["flops"]
            seconds += e - s
    if seconds <= 0:
        return None
    return 100.0 * flops / (seconds * rec.peaks["bf16_flops_per_s"])
