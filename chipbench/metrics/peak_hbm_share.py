"""The device's `peak_bytes_in_use` after the window, as a share of the
chip's HBM (`peaks.json`), in %."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return 100 * run.memory_peak_bytes / run.peaks["hbm_bytes"]
