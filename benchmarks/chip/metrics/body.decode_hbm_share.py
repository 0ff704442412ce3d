"""body.decode_hbm_share: the model HBM bytes of every decode step that ran
in the traced slice (the module's ``bytes``, from shapes) over their summed
device time times the chip's HBM bandwidth (``peaks.py``), in percent:
decode's share of the memory roofline.  Silent where the modules carry no
bytes.  Device trace.  Moves ``p50_ms``."""


def read(rec):
    if rec.peaks is None:
        return None
    lo, hi = rec.window
    moved = seconds = 0.0
    for name, s, e in rec.trace.modules:
        step = rec.modules.get(name)
        if step and step["kind"] == "decode" and "bytes" in step \
                and lo <= s and e <= hi:
            moved += step["bytes"]
            seconds += e - s
    if seconds <= 0:
        return None
    return 100.0 * moved / (seconds * rec.peaks["hbm_bytes_per_s"])
