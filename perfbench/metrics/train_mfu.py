"""train_mfu: operations that forward and backward require for the real
atoms and edges of the window's batches (``perfbench/flops.py``), over the
window's time, the chips in use and each chip's bf16 peak
(``perfbench/peaks.json``), in percent."""
from perfbench import flops, peaks


def read(record):
    if record["kind"] != "train":
        return None
    total = sum(flops.train_step_flops(record["config"],
                                       structures=c["structures"],
                                       atoms=c["atoms"], edges=c["edges"])
                for c in record["per_step"])
    peak = peaks.peak(record["device_kind"], "bf16_flops_per_s")
    return 100.0 * total / (record["window_s"] * record["chips"] * peak)
