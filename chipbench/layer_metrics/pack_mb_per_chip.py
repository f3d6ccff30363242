"""Resident cascade pack of the fullest chip at the window's end: the
largest value of the engine's ``pack_bytes_by_device`` gauge (each home
device's packs, summed over the shards it holds), in MB.  The cascade
reads these bytes; pow2 padding nearly doubles them."""


def read(run):
    by_dev = run.counters1.get("pack_bytes_by_device")
    if not by_dev:
        return None
    return max(by_dev.values()) / 1e6
