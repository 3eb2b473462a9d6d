"""hbm_peak_gb: ``peak_bytes_in_use`` of the chip once the window has
closed, before the reference runs, in 1e9 bytes."""


def read(run):
    peak = run.memory.get("peak_bytes_in_use")
    return None if not peak else peak / 1e9
