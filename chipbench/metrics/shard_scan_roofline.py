"""The per-shard ``divisibility_mask`` launches' share of their HBM
roofline on the mesh.  The trace reduction averages kernel time over the
device planes, so the logical bytes are taken per device too: the sum
over every shard's launches over the number of planes."""

from chipbench import work

_KERNEL = "divisibility_mask"


def read(record):
    tr = record.trace
    if tr is None or record.peaks is None or not tr["devices"]:
        return None
    seconds = tr["kernels"].get(_KERNEL, {}).get("seconds", 0.0)
    calls = [l for l in record.launches if l[0] == _KERNEL]
    if seconds <= 0 or not calls:
        return None
    per_device = sum(work.of(_KERNEL).hbm_bytes(*l[1:])
                     for l in calls) / tr["devices"]
    return 100.0 * per_device / record.peaks["hbm_bytes_per_s"] / seconds
